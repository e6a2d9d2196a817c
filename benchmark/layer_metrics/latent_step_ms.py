"""``latent_step_ms`` (layer: step): device time of the decode step's
program (``jit_tfos_decode_step_paged``: in this cell the latent block's
absorbed-attention step over the latent pool) per run of it in the traced
slice.  None where the capture holds no such program."""

from benchmark.lib import program_trace as P

PROGRAM = "jit_tfos_decode_step_paged"


def step_seconds(facts):
    """``(seconds per run, runs)`` of the step's program, or None."""
    dev = (P.load(facts) or {}).get("device")
    row = ((dev or {}).get("programs") or {}).get(PROGRAM)
    if not row or not row["runs"]:
        return None
    return row["seconds"] / row["runs"], row["runs"]


def read(facts):
    got = step_seconds(facts)
    return None if got is None else got[0] * 1e3
