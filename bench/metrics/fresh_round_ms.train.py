"""Device self time of a freshly communicated aggregation round: the ops
under the program's named scope ``l2gd.agg_fresh`` (the clients' loss,
uplink encode, server reduce, downlink and the update), over the
window's communicated rounds."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPE = "l2gd.agg_fresh"


def read(rec):
    t = sc.scope_seconds(sc.scope_times(rec) or {}, [SCOPE])
    if t is None or not rec.get("comm_rounds"):
        return None
    return 1e3 * t / rec["comm_rounds"]
