"""hbm_roofline.chat: least time a decode step needs (every weight once
plus each lane's live keys and values at 819e9 B/s, or its FLOPs at peak,
whichever is larger) over the decode program's device time per call
(trace).  Layer: decode program (XLA ops; no Pallas kernel is on this
path).  Moves itl_p95_ms."""

from bench.readers import step_roofline as read  # noqa: F401
