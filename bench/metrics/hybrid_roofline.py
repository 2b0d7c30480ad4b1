"""hybrid_roofline.<path> (%, device trace): the least time the chip
could take for the useful work of the generate waves in the traced
window, over the device time of the generate program
(``jit_slots_generate``) there. The useful work
(``bench/work/hybrid_block.py``) is that of the lanes each wave stepped:
every weight once a wave, each stepped session's SSM and conv state read
and written, and the keys and values of the positions its attention
read (the runner's ``kv_tokens_read``); bound by compute or by those
bytes against ``bench/peaks.py``. Lanes a wave carries but does not
step are work the program does and no session asked for."""

from bench import peaks
from bench.work import hybrid_block

GENERATE = "jit_slots_generate"


def read(run, path):
    p = run.profile
    w = (run.work or {}).get(path)
    if not p or not w or not w["waves"]:
        return None
    t = p["module_busy_s"].get(GENERATE, 0.0)
    if t <= 0:
        return None
    c = run.spec.config
    dtypes = c["assumed"]["dtypes"]
    flops = hybrid_block.step_flops(c, w["steps"], w["kv_tokens_read"])
    bytes_ = hybrid_block.wave_bytes(c, dtypes, w["waves"], w["steps"],
                                     w["kv_tokens_read"])
    least, _bound = peaks.least_time_s(flops, bytes_, run.device_kind)
    return 100.0 * least / t
