"""setup_s: process start until the engine is ready to serve (host
clock): JAX and the chip found, weights made from the seed, the engine
built and every shape warmed up, compilation included.  The arrivals an
open loop serves before its window are traffic, not set-up."""


def read(view):
    return view.setup_s
