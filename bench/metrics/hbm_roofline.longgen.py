"""hbm_roofline.longgen: least time a decode step needs (every weight
once, each lane's live keys and values, and each lane's DeltaNet state
read and written, at 819e9 B/s, or its FLOPs at peak, whichever is
larger) over the decode program's device time per call (trace).  Layer:
decode program (XLA ops; no Pallas kernel is on this path).  Moves
tok_per_s."""

from bench.readers import step_roofline as read  # noqa: F401
