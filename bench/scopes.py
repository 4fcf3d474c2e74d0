"""Device time under the program's named scopes.

The program names its layers with ``jax.named_scope``: ``model.attention``
and ``model.mlp`` in each decoder block, ``dmuon.stage_in``,
``dmuon.orthogonalize`` and ``dmuon.publish`` in the owner update.  A scope
is one ``/`` segment of the ``op_name`` metadata of every HLO instruction it
covers, in the forward, the backward and the remat recompute alike.

A reader needs two things from a traced run's ``ctx``: ``ctx["trace"]``,
self time per instruction on each device (``bench/drivers/train.py``'s
``_reduce_trace``), and ``ctx["op_names"]``, the step's ``{instruction:
op_name}`` map (``bench.trace.op_names`` of the compiled step's HLO text).
Where either is missing the readers read None.
"""

from __future__ import annotations


def _under(op_name: str, scopes) -> bool:
    segments = op_name.split("/")
    return any(s in segments for s in scopes)


def scope_ns(ops: dict, op_names: dict, *scopes: str) -> float:
    """Self ns of the instructions in ``ops`` (``{instruction: self ns}``)
    whose ``op_name`` has one of ``scopes`` as a whole ``/`` segment: a
    scope name inside another segment does not count, and an instruction
    counts once."""
    return sum(ns for name, ns in ops.items()
               if _under(op_names.get(name, ""), scopes))


def scopes_ms(ctx: dict, *scopes: str):
    """Device self time per step, in ms, under any of ``scopes``, mean over
    the cell's chips; None where the run was not traced, ``ctx`` holds no
    ``op_names`` map, or no instruction of the step carries one of the
    scopes (a program without them)."""
    red, names = ctx.get("trace"), ctx.get("op_names")
    if not red or not names or not any(_under(op, scopes)
                                       for op in names.values()):
        return None
    ns = [scope_ns(r["ops"], names, *scopes) for r in red.values()]
    return sum(ns) / len(ns) / ctx["steps"] * 1e-6
