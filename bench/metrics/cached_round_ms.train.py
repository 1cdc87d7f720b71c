"""Device self time of an aggregation round on the cached target: the
ops under the program's named scope ``l2gd.agg_cached`` (the clients'
loss and the update), over the window's cached rounds (its steps less
the local steps and the communicated rounds)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPE = "l2gd.agg_cached"


def read(rec):
    t = sc.scope_seconds(sc.scope_times(rec) or {}, [SCOPE])
    rounds = rec.get("steps", 0) - rec.get("local_steps", 0) \
        - rec.get("comm_rounds", 0)
    if t is None or rounds <= 0:
        return None
    return 1e3 * t / rounds
