"""``step_launch_ms`` (layer: serving scheduler): the median over the
traced slice's decode iterations of the step's launch, on the host's
clock (``lib/aligned_trace.py``): from the begin of
``tfos/decode/step_dispatch`` (so the dispatch call itself is included)
to the start of that iteration's ``jit_tfos_decode_step_paged`` on the
device, moved onto the host's clock by the program's clock probes.  The
``[bench:aligned_trace]`` line gives the part after the call returns.
None without a capture or without enough probes in it."""

from benchmark.lib import aligned_trace as A


def read(facts):
    return (A.load(facts) or {}).get("launch_ms")
