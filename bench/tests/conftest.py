import pytest


@pytest.fixture
def chip_precision(monkeypatch):
    """On the chip the program's float32 anneal product is one bfloat16
    MXU pass (the precision the configurations state); the CPU computes
    it in full float32. Tests that drive whole runs on the CPU ask the
    program for its bfloat16 operand path, which is what the chip does."""
    from repro.core import device_model

    init = device_model.DeviceModel.__init__

    def bf16_init(self, *args, compute_dtype="bfloat16", **kw):
        init(self, *args, compute_dtype=compute_dtype, **kw)

    monkeypatch.setattr(device_model.DeviceModel, "__init__", bf16_init)
