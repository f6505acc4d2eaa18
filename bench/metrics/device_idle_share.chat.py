"""device_idle_share.chat: percent of the traced window in which no
operation ran on the device (trace).  Layer: engine host loop
(serve/engine.DecodeEngine).  Moves itl_p95_ms."""

from bench.readers import idle_share as read  # noqa: F401
