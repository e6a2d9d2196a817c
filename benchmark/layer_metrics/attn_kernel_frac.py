"""``attn_kernel_frac`` (layer: kernels): device self time of the flash
attention kernels, found by the names the program gives them
(``tfos_flash_fwd``, ``tfos_flash_bwd_dq``, ``tfos_flash_bwd_dkv``), over
the device's busy time in the traced slice.  None where no operation
carries such a name (a program without named kernels, or one that runs
none)."""

from benchmark.lib import program_trace as P

PREFIX = "tfos_flash_"


def read(facts):
    dev = (P.load(facts) or {}).get("device")
    if not dev or not dev["busy_s"]:
        return None
    mine = [s for k, s in dev["kernels"].items() if k.startswith(PREFIX)]
    return sum(mine) / dev["busy_s"] if mine else None
