"""Device self time of the uplink exchange between chips per freshly
communicated round: the ops under the program's named scope ``gather``
(``aggregation._gather_payloads``, the client-sharded engine's
all_gather of the wire payloads), averaged over the chips the window
traced, over the window's communicated rounds.  A program with no
``gather`` scope (a cell on one chip) reads nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPE = "gather"


def read(rec):
    per_device = sc.device_scope_times(rec)
    if not per_device or not rec.get("comm_rounds"):
        return None
    ts = [sc.scope_seconds(s, [SCOPE]) for s in per_device]
    if any(t is None for t in ts):
        return None
    return 1e3 * sum(ts) / len(ts) / rec["comm_rounds"]
