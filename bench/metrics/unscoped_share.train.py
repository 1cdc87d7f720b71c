"""Share of the traced window's device self time spent in ops under
none of the rollout's top-level named scopes (``l2gd.local``,
``l2gd.agg_fresh``, ``l2gd.agg_cached``, ``rollout.streams``): the
scan's own loop and carry, the switch, and the copies the compiler adds
between them.  A program with none of those scopes reads nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import scopes as sc  # noqa: E402

SCOPES = ["l2gd.local", "l2gd.agg_fresh", "l2gd.agg_cached",
          "rollout.streams"]


def read(rec):
    scopes = sc.scope_times(rec) or {}
    scoped = sc.scope_seconds(scopes, SCOPES)
    total = sum(scopes.values())
    if scoped is None or total <= 0:
        return None
    return 100.0 * (total - scoped) / total
