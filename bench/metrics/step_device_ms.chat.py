"""step_device_ms.chat: device time per call of the decode program
(trace).  Layer: model step (models/decoder.decode_step).  Moves
itl_p95_ms."""

from bench.readers import step_device_ms as read  # noqa: F401
