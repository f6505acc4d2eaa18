"""compile_s: seconds spent building programs during set-up (tracing,
lowering, and compiling or loading from the persistent cache), from
JAX's own duration events.  Layer: entry (launch/serve build and
warm-up).  Moves setup_s."""


def read(view):
    return view.setup_compile_s
