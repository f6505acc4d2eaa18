"""step_mfu.chat: model FLOPs of the work done inside the window (each
prompt prorated over its prefill, each output fed back), over the window
at the bf16 peak (host clock and counts).  Layer: model step, whole-step
share of peak.  Moves itl_p95_ms."""

from bench.readers import step_mfu as read  # noqa: F401
