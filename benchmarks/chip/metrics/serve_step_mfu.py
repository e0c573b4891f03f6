"""The serve step's model FLOPs (active parameters x 2 per slot in use,
and attention over the live lengths, from the configuration's
``decode_step_flops``, ``harness.counts_for``) over its device time and
the bf16 peak."""
import trace_reduce

from importlib import util as _u
from pathlib import Path as _P

_spec = _u.spec_from_file_location(
    "_roof", _P(__file__).with_name("serve_step_roofline_pct.py"))
_roof = _u.module_from_spec(_spec)
_spec.loader.exec_module(_roof)


def read(ctx):
    sec, n = trace_reduce.module_time(ctx.trace, r"jit_serve_step")
    if not n or not sec:
        return None
    work = [ctx.counts.decode_step_flops(ctx.config, x)
            for x in _roof.calls(ctx)]
    if not work:
        return None
    per_call = sum(work) / len(work)
    return 100.0 * per_call * n / sec / ctx.peak["bf16_flops"]
