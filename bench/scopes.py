"""Device self time per ``jax.named_scope`` of the program, in a traced
run's window.

The chip trace's op events carry no metadata, but the compiled HLO of
every program the process ran is still loaded in it, after the window,
and each instruction's ``op_name`` there (``jit(f)/while/body/cond/
branch_0_fun/l2gd.local/grad/vmap(transpose(jvp(model.embed)))/
dot_general``) names the chain of scopes it ran under.  An op event
takes the scope path of the instruction of its name, in the program
that holds the most of the window's device time, and its self time
(:func:`bench.trace.self_times`) goes to that path.  A program without
named scopes gives one path, the unscoped one, and a reader of a scope
then reads nothing.
"""
from __future__ import annotations

import gc
import re
import time
from collections import defaultdict

from bench import trace as tl

#: scope path of an op under no named scope
UNSCOPED = ""
#: the top-level scopes of a rollout chunk (the three branches of the
#: protocol step and the random streams made before the scan); an op
#: under none of them is unscoped
TOP_SCOPES = ("l2gd.local", "l2gd.agg_fresh", "l2gd.agg_cached",
              "rollout.streams")
#: components of an op_name that JAX itself adds around control flow,
#: rematerialization and a program mapped over a mesh, never named scopes
_JAX_PARTS = frozenset({"while", "body", "cond", "closed_call", "checkpoint",
                        "rematted_computation", "shard_map"})
#: a transformation's wrapper around the scopes inside it, as in
#: ``vmap(transpose(jvp(model.ffn)))``
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
#: a scope name: lower-case words joined by dots
_SCOPE = re.compile(r"^[a-z_][a-z0-9_]*(?:\.[a-z0-9_]+)*$")
#: a branch of a ``lax.cond`` or ``lax.switch``, as JAX names it
_BRANCH = re.compile(r"^branch_\d+_fun$")
#: one instruction of compiled HLO text, with its op_name metadata
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                     r'metadata=\{[^}]*?op_name="([^"]*)"')
#: one instruction of compiled HLO text, with or without metadata
_HLO_BARE_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ')
#: the branch computations a conditional names in compiled HLO text
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
#: the first line of a computation in compiled HLO text
_HLO_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$')


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction name: op_name metadata} of compiled HLO text (the
    instruction names are the device ops' event names).

    The compiler leaves some instructions without metadata: copies,
    layout changes, and fusions it forms itself, such as a gather's
    scatter in the backward pass.  Such an instruction takes the scope
    its computation runs under, where the computation has one: the
    op_name components shared by its ops under a top-level scope, if it
    is a branch of a conditional (the protocol's switch) or nine in ten
    of its ops with metadata lie under a top-level scope (the body of a
    loop inside a branch; constants JAX hoisted there carry none).  So a
    copy inside a branch counts under that branch, and one in the scan's
    body, beside the switch, or in the entry computation under none."""
    out, members, comp = {}, defaultdict(list), None
    branches = set()
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        for found in _HLO_BRANCHES.findall(line):
            branches.update(c.strip().lstrip("%") for c in found.split(","))
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = m.group(2).split(";", 1)[0]
            members[comp].append(m.group(1))
            continue
        m = _HLO_BARE_OP.match(line)
        if m:
            members[comp].append(m.group(1))
    for comp, ops in members.items():
        named = [out[op].split("/") for op in ops if op in out]
        scoped = [n[:-1] for n in named
                  if top_scope(scope_path("/".join(n)))]
        if not scoped or (comp not in branches
                          and 10 * len(scoped) < 9 * len(named)):
            continue
        shared = []
        for parts in zip(*scoped):
            if len(set(parts)) > 1:
                break
            shared.append(parts[0])
        for op in ops:
            if op not in out:
                out[op] = "/".join(shared + [op])
    return out


def scope_path(op_name: str) -> str:
    """The named scopes of one op_name, outermost first, joined by "/".
    Transformation wrappers are stripped, so a backward op counts under
    the scopes of its forward; the last component (the primitive), JAX's
    own control-flow parts and nested ``jit(...)`` names are left out.
    Where the compiler merged ops, the metadata joins their names with
    ";" and the first is taken."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    out = []
    for part in parts:
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if _SCOPE.match(part) and part not in _JAX_PARTS \
                and not _BRANCH.match(part):
            out.append(part)
    return "/".join(out)


def op_scopes(op_names: dict) -> dict:
    """{op name: scope path} from {op name: op_name metadata}."""
    return {op: scope_path(name) for op, name in op_names.items()}


def scope_self_times(events, scopes: dict) -> dict:
    """Device self time per scope path, in seconds, by the nesting rule
    of :func:`bench.trace.self_times`; an op with no entry in ``scopes``
    counts as unscoped."""
    out = defaultdict(float)
    for op, ns in tl.self_times(events).items():
        out[scopes.get(op, UNSCOPED)] += ns * 1e-9
    return dict(out)


def scope_seconds(scope_s: dict, names, within: str = None):
    """Self seconds of the scope paths that hold one of ``names`` (and
    ``within``, where given) as a component, or None where no path does:
    a program without those scopes reads nothing, never a zero."""
    hits = [t for path, t in scope_s.items()
            if set(path.split("/")) & set(names)
            and (within is None or within in path.split("/"))]
    return sum(hits) if hits else None


def top_scope(path: str) -> str:
    """The top-level scope a scope path lies under, or UNSCOPED."""
    for part in path.split("/"):
        if part in TOP_SCOPES:
            return part
    return UNSCOPED


def scope_summary(scope_s: dict, busy_s: float) -> str:
    """One line: each top-level scope's self seconds and share of busy
    time, then every scope path's, largest first."""
    top = defaultdict(float)
    for path, t in scope_s.items():
        top[top_scope(path)] += t

    def fmt(items):
        return ", ".join(f"{k or 'unscoped'} {t:.4f} s {100 * t / busy_s:.2f}%"
                         for k, t in sorted(items, key=lambda kv: -kv[1]))
    return (f"device self time by scope (total {sum(top.values()):.4f} s, "
            f"busy {busy_s:.4f} s): top: {fmt(top.items())}; paths: "
            f"{fmt(scope_s.items())}")


# ---------------------------------------------------------------------------
# the HLO of the programs this process ran
# ---------------------------------------------------------------------------

def loaded_hlo_texts():
    """The compiled HLO text, with each instruction's metadata, of every
    program JAX holds loaded in this process (its jit caches keep the
    executables they ran)."""
    from jax._src.interpreters import pxla
    from jax._src.lib import _jax
    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    for obj in gc.get_objects():
        if isinstance(obj, pxla.MeshExecutable):
            for module in obj.xla_executable.hlo_modules():
                yield module.to_string(opts)


def program_op_scopes(events, texts) -> dict:
    """{op name: scope path} of the program, among the HLO ``texts``,
    whose instructions cover the most self time of ``events``; {} where
    there is none."""
    own = tl.self_times(events)
    best, best_ns = {}, 0
    for text in texts:
        names = hlo_op_names(text)
        ns = sum(t for op, t in own.items() if op in names)
        if ns > best_ns:
            best, best_ns = names, ns
    return op_scopes(best)


_LAST = [None, None, None]  # [the trace reduction read last, its scope
#                              times, its ops' scope paths]


def scope_times(rec: dict):
    """{scope path: device self seconds} of a traced run's window, or
    None for an untraced run or where no loaded program ran its ops.
    The first call for a trace prints each scope's self seconds and
    share of busy time on one line, with the seconds the reading took."""
    red = rec.get("trace")
    if not red or not red.get("events"):
        return None
    if _LAST[0] is not red:
        t0 = time.perf_counter()
        sc = program_op_scopes(red["events"], loaded_hlo_texts())
        out = scope_self_times(red["events"], sc) if sc else None
        if out:
            print(f"{scope_summary(out, red['busy_s'])}; read in "
                  f"{time.perf_counter() - t0:.2f} s", flush=True)
        _LAST[:] = [red, out, sc]
    return _LAST[1]


def device_scope_times(rec: dict):
    """[{scope path: device self seconds}] of each device the window
    traced, by the scope paths of the program :func:`scope_times`
    reads (a program across chips runs the same ops on each), or None
    where that reads nothing."""
    if scope_times(rec) is None:
        return None
    red = rec["trace"]
    return [scope_self_times(evs, _LAST[2])
            for evs in red.get("device_events", [red["events"]])]
