"""step_mfu.longgen: model FLOPs of the work done inside the window (each
prompt prorated over its prefill, each output fed back; the routed
experts counted at this chip's held share), over the window at the bf16
peak (host clock and counts).  Layer: model step, whole-step share of
peak.  Moves tok_per_s."""

from bench.readers import step_mfu as read  # noqa: F401
