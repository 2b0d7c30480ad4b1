"""hybrid_mfu.<path> (%, device trace): the model operations of the
steps the traced window answered (``bench/work/hybrid_block.py``: every
layer, the head, and attention over the positions each step read), over
the chip's peak (``bench/peaks.py``) times the device busy time of the
session path's programs (generate, lane insert and extract, prefill)."""

from bench import peaks
from bench.work import hybrid_block

PROGRAMS = ("jit_slots_generate", "jit_slots_insert", "jit_slots_extract",
            "jit_prefill")


def read(run, path):
    p = run.profile
    w = (run.work or {}).get(path)
    if not p or not w:
        return None
    t = sum(s for mod, s in p["module_busy_s"].items() if mod in PROGRAMS)
    flops = hybrid_block.step_flops(run.spec.config, w["steps"],
                                    w["kv_tokens_read"])
    if flops <= 0 or t <= 0:
        return None
    return 100.0 * flops / (peaks.peaks(run.device_kind)["flops_per_s"] * t)
