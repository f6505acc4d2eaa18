"""device_idle_share.offline: percent of the traced window in which no
operation ran on the device (trace).  Layer: engine host loop
(serve/engine.DecodeEngine).  Moves tok_per_s."""

from bench.readers import idle_share as read  # noqa: F401
