"""``prefill_ms_per_ktok`` (layer: step): device milliseconds of the
prefill program (``jit_tfos_prefill``) in the traced slice per thousand
PADDED prompt tokens it ran there (the ``tokens`` of the slice's
``tfos/decode/prefill`` spans: rows x sequence bucket, what the program
computes whatever the prompts' own lengths).  In the hybrid cell: what
the chunked delta rule, the expanded latent path and the expert dispatch
cost together, at admission.  None without a capture, where the program
writes no such span or argument, or where no prefill fell into the
slice."""

from benchmark.lib import program_trace as P

PROGRAM = "jit_tfos_prefill"


def read(facts):
    reduced = P.load(facts)
    row = (((reduced or {}).get("device") or {}).get("programs")
           or {}).get(PROGRAM)
    tokens = ((P.span(reduced, "tfos/decode/prefill") or {}).get("args")
              or {}).get("tokens")
    if not row or not row["runs"] or not tokens:
        return None
    return row["seconds"] * 1e3 / (tokens / 1e3)
