"""Device self time per named scope: the HLO text's op_name metadata,
the scope paths, the nesting of self times, the programs loaded in the
process, and the five scope readers (no chip, no TPU library)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402
from bench import scopes as sc  # noqa: E402
from bench import trace as tl  # noqa: E402

MS = 1_000_000

SCOPE_METRICS = ["local_step_ms.train", "fresh_round_ms.train",
                 "cached_round_ms.train", "moe_routing_ms_per_step.train",
                 "unscoped_share.train"]


def _rec(**kw):
    rec = {"steps": 20, "local_steps": 10, "comm_rounds": 4,
           "trace": {"window_s": 2.0, "busy_s": 1.5, "events": []}}
    rec.update(kw)
    return rec


HLO = """HloModule jit_roll, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(roll)/while/body/closed_call/cond/branch_0_fun/l2gd.local/grad/mul"}
}

%branch_0 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %copy.3 = f32[8]{0} copy(%p)
  %fusion.1 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(roll)/while/body/closed_call/cond/branch_0_fun/l2gd.local/grad/vmap(transpose(jvp(model.ffn)))/moe.route/dot_general" stack_frame_id=4}
  ROOT %sub.2 = f32[8]{0} subtract(%fusion.1, %p), metadata={op_name="jit(roll)/while/body/closed_call/cond/branch_0_fun/l2gd.local/update/sub;jit(roll)/other/sub"}
}

%branch_1 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %constant.9 = f32[]{:T(128)} constant(1), metadata={op_name="jit(roll)/while/body/closed_call"}
  %copy.8 = f32[8]{0} copy(%p.1)
  %natural_fused_pallas.7 = f32[8]{0} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(roll)/while/body/closed_call/cond/branch_1_fun/l2gd.agg_fresh/average/uplink_encode/jit(natural_fused_pallas)/natural_fused_pallas/pallas_call"}
  ROOT %add.4 = f32[8]{0} add(%natural_fused_pallas.7, %p.1), metadata={op_name="jit(roll)/while/body/closed_call/cond/branch_1_fun/l2gd.agg_fresh/apply/add"}
}

ENTRY %main.5 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy.552 = f32[8]{0} copy(%x)
  %rng.1 = u32[2]{0} add(%x, %x), metadata={op_name="jit(roll)/rollout.streams/jit(_threefry_split)/add"}
  %conditional.1 = f32[8]{0} conditional(%x, %x), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(roll)/while/body/closed_call/cond"}
  ROOT %while.2 = f32[8]{0} while(%copy.552), condition=%cond, body=%body, metadata={op_name="jit(roll)/while"}
}
"""


def test_hlo_op_names_read_metadata_and_place_bare_ops():
    names = sc.hlo_op_names(HLO)
    assert names["mul.9"].endswith("l2gd.local/grad/mul")
    assert names["natural_fused_pallas.7"].endswith("/pallas_call")
    # a copy the compiler added takes the scope its computation runs
    # under: a branch of the switch, however many of its ops JAX left
    # unscoped, or a computation nine in ten of whose ops are scoped
    assert sc.scope_path(names["copy.3"]) == "l2gd.local"
    assert sc.scope_path(names["copy.8"]) == "l2gd.agg_fresh"
    # the entry computation, half scoped, gives none
    assert sc.scope_path(names.get("copy.552", "")) == sc.UNSCOPED
    branchless = HLO.replace("branch_computations={%branch_0, %branch_1}",
                             "")
    assert "copy.8" not in sc.hlo_op_names(branchless)
    assert sc.scope_path(sc.hlo_op_names(branchless)["copy.3"]) == \
        "l2gd.local"


def test_scope_path_strips_wrappers_jit_names_and_jax_parts():
    names = sc.hlo_op_names(HLO)
    paths = sc.op_scopes(names)
    assert paths["fusion.1"] == "l2gd.local/grad/model.ffn/moe.route"
    assert paths["sub.2"] == "l2gd.local/update"         # first of a merge
    assert paths["natural_fused_pallas.7"] == \
        "l2gd.agg_fresh/average/uplink_encode/natural_fused_pallas"
    assert paths["rng.1"] == "rollout.streams"
    assert paths["while.2"] == sc.UNSCOPED
    assert sc.scope_path("cond/branch_2_fun/l2gd.agg_cached/loss/"
                         "vmap(jvp())/model.embed/gather") == \
        "l2gd.agg_cached/loss/model.embed"


def test_scope_self_times_nest_like_self_times_and_cover_the_device():
    paths = sc.op_scopes(sc.hlo_op_names(HLO))
    evs = [("%while.2 = f32[8] while(...)", 0, 100 * MS),
           ("%copy.3 = f32[8] copy(...)", 5 * MS, 10 * MS),
           ("%fusion.1 = f32[8] fusion(...)", 20 * MS, 30 * MS),
           ("%natural_fused_pallas.7 = f32[8] custom-call(...)", 60 * MS,
            20 * MS),
           ("%copy.552 = f32[8] copy(...)", 100 * MS, 5 * MS),
           ("%stack.1 = s32[2] concatenate(...)", 110 * MS, 2 * MS)]
    out = sc.scope_self_times(evs, paths)
    assert out == {
        sc.UNSCOPED: pytest.approx((40 + 5 + 2) * 1e-3),
        "l2gd.local": pytest.approx(10e-3),
        "l2gd.local/grad/model.ffn/moe.route": pytest.approx(30e-3),
        "l2gd.agg_fresh/average/uplink_encode/natural_fused_pallas":
            pytest.approx(20e-3)}
    assert sum(out.values()) == pytest.approx(
        tl.busy_ns(evs, 0, 200 * MS) * 1e-9)
    assert sc.top_scope("l2gd.local/grad") == "l2gd.local"
    assert sc.top_scope("model.ffn/moe.route") == sc.UNSCOPED
    assert sc.scope_seconds(out, ["moe.route"], within="l2gd.local") == \
        pytest.approx(30e-3)
    assert sc.scope_seconds(out, ["moe.route"], within="l2gd.agg_fresh") \
        is None
    line = sc.scope_summary(out, 0.097)
    assert "l2gd.local 0.0400 s 41.24%" in line and "unscoped 0.0470 s" in line


SCOPE_S = {"": 1.0, "l2gd.local/grad/model.ffn/moe.route": 2.0,
           "l2gd.local/grad/model.ffn/moe.dispatch": 0.5,
           "l2gd.local/grad/model.ffn/moe.experts": 3.0,
           "l2gd.local/update": 0.5,
           "l2gd.agg_fresh/loss/model.ffn/moe.combine": 0.25,
           "l2gd.agg_fresh/average/uplink_encode": 0.75,
           "l2gd.agg_cached/apply": 0.4, "rollout.streams": 0.1}


@pytest.fixture
def scoped(monkeypatch):
    """The readers see SCOPE_S as the run's scope times."""
    monkeypatch.setattr(sc, "scope_times",
                        lambda rec: SCOPE_S if rec.get("trace") else None)


def test_branch_readers_divide_scope_time_by_their_steps(scoped):
    assert harness.reader("local_step_ms.train")(_rec()) == \
        pytest.approx(1e3 * 6.0 / 10)
    assert harness.reader("fresh_round_ms.train")(_rec()) == \
        pytest.approx(1e3 * 1.0 / 4)
    assert harness.reader("cached_round_ms.train")(_rec()) == \
        pytest.approx(1e3 * 0.4 / 6)
    # routing is the local step's route, dispatch and combine, not the
    # experts' matmuls nor an aggregation round's loss pass
    assert harness.reader("moe_routing_ms_per_step.train")(_rec()) == \
        pytest.approx(1e3 * 2.5 / 10)
    assert harness.reader("unscoped_share.train")(_rec()) == \
        pytest.approx(100 * 1.0 / 8.5)


@pytest.mark.parametrize("metric", SCOPE_METRICS)
def test_scope_readers_read_nothing_without_their_scope(metric, monkeypatch):
    read = harness.reader(metric)
    # an untraced run reads nothing
    assert read(_rec(trace=None)) is None
    # nor does a program with no named scopes (the parent of the scopes):
    # every op is unscoped, never a zero
    bare = HLO.replace("l2gd.", "x").replace("rollout.streams", "x")
    monkeypatch.setattr(sc, "loaded_hlo_texts", lambda: [bare])
    evs = [("%fusion.1 = f32[8] fusion(...)", 0, 30 * MS),
           ("%sub.2 = f32[8] subtract(...)", 40 * MS, 5 * MS)]
    assert read(_rec(trace={"busy_s": 0.035, "events": evs})) is None
    # nor a trace whose ops no loaded program holds
    monkeypatch.setattr(sc, "loaded_hlo_texts", lambda: [])
    assert read(_rec(trace={"busy_s": 0.035, "events": evs})) is None


def test_scope_readers_read_nothing_without_their_steps(scoped):
    assert harness.reader("local_step_ms.train")(
        _rec(local_steps=0)) is None
    assert harness.reader("fresh_round_ms.train")(
        _rec(comm_rounds=0)) is None
    assert harness.reader("cached_round_ms.train")(_rec(steps=14)) is None
    assert harness.reader("moe_routing_ms_per_step.train")(
        _rec(local_steps=0)) is None


def test_the_collective_reader_averages_gather_time_over_chips_per_round(
        monkeypatch):
    """``collective_ms_per_round.train``: each device's self time under
    the ``gather`` scope (the shard_map's own name left out of the
    path), averaged over the devices, per communicated round; nothing
    where the program has no such scope or the run no rounds."""
    read = harness.reader("collective_ms_per_round.train")
    gathered = HLO.replace(
        "branch_1_fun/l2gd.agg_fresh/average/uplink_encode/",
        "branch_1_fun/l2gd.agg_fresh/average/gather/").replace(
        "jit(roll)/while", "jit(roll)/shard_map/while")
    monkeypatch.setattr(sc, "loaded_hlo_texts", lambda: [gathered])

    def dev(gather_ms):
        return [("%fusion.1 = f32[8] fusion(...)", 0, 30 * MS),
                ("%natural_fused_pallas.7 = f32[8] custom-call(...)",
                 40 * MS, gather_ms * MS)]
    rec = _rec(trace={"busy_s": 0.04, "events": dev(8),
                      "device_events": [dev(8), dev(12), dev(6), dev(10)]})
    assert read(rec) == pytest.approx(9.0 / 4)
    assert "l2gd.agg_fresh/average/gather/natural_fused_pallas" in \
        sc.scope_times(rec)
    assert read(_rec(trace=rec["trace"], comm_rounds=0)) is None
    assert read(_rec(trace=None)) is None
    # a one-chip program has no gather scope: nothing, never a zero
    monkeypatch.setattr(sc, "loaded_hlo_texts", lambda: [HLO])
    assert read(_rec(trace={"busy_s": 0.04, "events": dev(8),
                            "device_events": [dev(8)]})) is None


def test_scope_times_pick_the_program_that_ran_and_print_once(
        monkeypatch, capsys):
    other = HLO.replace("%fusion.1 =", "%fusion.7 =")
    monkeypatch.setattr(sc, "loaded_hlo_texts", lambda: [other, HLO])
    evs = [("%fusion.1 = f32[8] fusion(...)", 0, 30 * MS),
           ("%sub.2 = f32[8] subtract(...)", 40 * MS, 5 * MS),
           ("%while.2 = f32[8] while(...)", 50 * MS, 5 * MS)]
    rec = _rec(trace={"busy_s": 0.04, "events": evs})
    want = {"l2gd.local/grad/model.ffn/moe.route": pytest.approx(30e-3),
            "l2gd.local/update": pytest.approx(5e-3),
            sc.UNSCOPED: pytest.approx(5e-3)}
    assert sc.scope_times(rec) == want
    assert sc.scope_times(rec) == want
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("device self time by scope")
    assert harness.reader("unscoped_share.train")(rec) == \
        pytest.approx(100 * 5 / 40)


def test_loaded_programs_carry_their_scopes_on_this_backend():
    """A jitted program's executable stays loaded after it ran, and its
    text gives each fused op its scope, backward ops and the switch's
    branches included."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def branch_local(w):
        with jax.named_scope("l2gd.local"):
            with jax.named_scope("grad"):
                g = jax.grad(lambda v: jnp.sum(jnp.tanh(v @ v)))(w)
            return w - 0.1 * g

    def branch_fresh(w):
        with jax.named_scope("l2gd.agg_fresh"):
            return 0.5 * (w + jnp.mean(w, axis=0))

    def step(w, i):
        return lax.switch(i % 2, [branch_local, branch_fresh], w), None

    @jax.jit
    def scoped_roll(w):
        return lax.scan(step, w, jnp.arange(4))[0]

    jax.block_until_ready(scoped_roll(jnp.ones((16, 16))))
    texts = [t for t in sc.loaded_hlo_texts()
             if t.startswith("HloModule jit_scoped_roll")]
    assert len(texts) == 1
    names = sc.hlo_op_names(texts[0])
    paths = set(sc.op_scopes(names).values())
    assert any(p.startswith("l2gd.local/grad") for p in paths)
    assert any(p.startswith("l2gd.agg_fresh") for p in paths)
    # the events of that program's ops attribute to its scopes
    evs = [(f"%{op} = f32[16,16] x(...)", k * MS, MS)
           for k, op in enumerate(sorted(names))]
    out = sc.scope_times(_rec(trace={"busy_s": len(evs) * 1e-3,
                                     "events": evs}))
    assert sum(out.values()) == pytest.approx(len(evs) * 1e-3)
    assert any(sc.top_scope(p) == "l2gd.local" for p in out)
