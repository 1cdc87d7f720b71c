"""Device self time of the local step per local protocol step of the
traced window: the ops under the program's named scope ``l2gd.local``
(forward, backward and update of every client), over the window's
local steps."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPE = "l2gd.local"


def read(rec):
    t = sc.scope_seconds(sc.scope_times(rec) or {}, [SCOPE])
    if t is None or not rec.get("local_steps"):
        return None
    return 1e3 * t / rec["local_steps"]
