"""Device self time of the MoE routing in the local step, per local
protocol step of the traced window: the ops under the program's named
scopes ``moe.route`` (router, top-k, capacity positions), ``moe.dispatch``
(tokens into the experts' buffers) and ``moe.combine`` (back, weighted by
gate), forward and backward, inside ``l2gd.local``.  The expert matmuls
(``moe.experts``) are not routing; the aggregation rounds' loss passes
are not the local step.  Ops the compiler rewrites without metadata
(scatter fusions, a long cumsum's reduce-window) count under the
enclosing ``l2gd.local/grad``, not here (bench/scopes.hlo_op_names)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPES = ["moe.route", "moe.dispatch", "moe.combine"]
WITHIN = "l2gd.local"


def read(rec):
    t = sc.scope_seconds(sc.scope_times(rec) or {}, SCOPES, within=WITHIN)
    if t is None or not rec.get("local_steps"):
        return None
    return 1e3 * t / rec["local_steps"]
