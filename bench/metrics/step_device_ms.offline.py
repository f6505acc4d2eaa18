"""step_device_ms.offline: device time per call of the decode program
(trace).  Layer: model step (models/decoder.decode_step).  Moves
tok_per_s."""

from bench.readers import step_device_ms as read  # noqa: F401
