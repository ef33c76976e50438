"""Device time per execution of the train-step program (``step`` of
``make_train_step``) spent in the MoE layers' row permute: the operations
whose HLO instruction carries a ``pallas_call`` name of
``kernels/moe_permute.py`` (``moe_permute_*``: the combine, its
transpose and the flattening it reads), inside the window, over the
window's ``step`` executions. A program without the kernels reads nothing."""
import re

KERNEL = re.compile(r"%?moe_permute_\w*(\.\d+)? = ")


def read(rec, ctx):
    if rec.trace is None:
        return None
    _, n = rec.trace.program("step")
    total = sum(t for d in rec.trace.devices for name, t in d.ops.items()
                if KERNEL.match(name))
    if not n or not total:
        return None
    return 1e3 * total / n
