"""``step_return_ms`` (layer: serving scheduler): the median over the
traced slice's decode iterations of the ids' return, on the host's clock
(``lib/aligned_trace.py``): from the end of the iteration's last program
on the device (the pick, ``jit_tfos_pick``), moved onto the host's clock
by the program's clock probes, to the end of ``tfos/decode/logits_fetch``.
None without a capture or without enough probes in it."""

from benchmark.lib import aligned_trace as A


def read(facts):
    return (A.load(facts) or {}).get("return_ms")
