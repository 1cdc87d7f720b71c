"""Train cells: compressed L2GD (Algorithm 1) over stacked clients,
driven chunk by chunk through the program's scanned rollout as its
driver runs it (``repro.fl.l2gd_driver``'s scan mode): stack the
chunk's batches, dispatch one jitted ``rollout_l2gd`` chunk, fetch the
trace buffers, replay the xi trace into the program's ``BitsLedger``.

A cell on more than one chip runs the client-sharded engine instead,
as ``repro.launch.steps.build_sharded_rollout_fn`` jits it: the clients
are sharded over a ``clients`` mesh of the cell's chips (a whole number
of clients per chip), the state is donated to each chunk, and a fresh
round's uplink payloads cross the chips in one ``all_gather``.  The
weights are made in that layout and each chunk's batches go straight to
their shards, so no device ever holds every client.

The configuration's ``"model"`` key names the module
``bench/models/<model>.py`` that maps its keys to the program's
``ArchConfig`` and gives its plain reference and FLOP count; nothing
here reads a configuration key.

Set-up builds that one jitted chunk, runs the first chunk from the
seed's weights (it compiles, or loads from the compile cache) and one
more to warm, and hands the same object and state to the window.  The
window is whole chunks and ends at the first chunk boundary after
``--seconds``.

Correctness.  The cell's protocol key makes the first chunk 14 local
steps, then a freshly communicated aggregation round and a round on the
cached target (the three branches of Algorithm 1).  After the window
the plain reference (``bench/reference``) follows those 14 local steps
from the same weights and batches, and the run compares:

* ``loss_gap``: each step's mean client loss, program against
  reference, the worst relative gap over the first 15 steps (the
  cached round's loss is taken at a point only the program's target
  reaches);
* ``update_gap``: each leaf's change over the 14 local steps (both
  rounds inverted with the program's own target), as the
  norm of the program's change against the norm of the reference's, by
  the worst leaf, relative to the larger of that leaf's and the median
  leaf's reference norm (leaves whose first reference gradient is under
  a thousandth of the median leaf's are left out);
* ``agg_err_z``: the relative error of the compressed aggregation
  target the program produced in the round, ||t - mean x|| / ||mean x||,
  against the reference's own compression of its own mean: the
  relative gap, in units of 1/sqrt(d) for a flat model of d elements
  (the size of its fluctuation from seed to seed);
* ``agg_bias_z``: the correlation of the program's target error with
  the mean, in units of 1/sqrt(d) (an unbiased compression of
  independent elements leaves it of order 1);
* ``protocol_mismatches``: the realized xi of the first chunk against
  the expected one, and every chunk's branch counters against those
  replayed from its xi trace (exact).

A wrong cached round moves the inverted x14 off the reference's and the
target's error off the mean's: both rounds are covered.
"""
from __future__ import annotations

import functools
import gc
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, peaks, weights
from bench import trace as trace_lib
from bench.reference import protocol as ref_protocol
from bench.traffic.tokens import TokenStream

from repro.core import L2GDHyper, init_state, make_compressor
from repro.core.codec import make_plan
from repro.core.rollout import rollout_l2gd, rollout_l2gd_sharded
from repro.fl.ledger import BitsLedger
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_client_mesh
from repro.launch.sharding import (client_sharded_batch_shardings,
                                   client_sharded_shardings)
from repro.models import init_params, loss_fn

# ---------------------------------------------------------------------------
# the program under test (module attributes, so that a test can plant a
# fault underneath a whole run)
# ---------------------------------------------------------------------------

def program_grad_fn(cfg):
    """Per-client (loss, grads), as ``repro.launch.train`` builds it."""
    def grad_fn(p, b):
        (loss, _), g = jax.value_and_grad(
            lambda q: loss_fn(q, cfg, b), has_aux=True)(p)
        return loss, g
    return grad_fn


def program_chunk(grad_fn, up_plan, down_plan, length: int,
                  local_steps: int):
    """One scanned chunk, jitted as the program's driver jits it."""
    return jax.jit(functools.partial(
        rollout_l2gd, grad_fn=grad_fn, steps=length, client_comp=up_plan,
        master_comp=down_plan, batch_axis=0, participation=None,
        local_steps=local_steps))


def program_sharded_chunk(grad_fn, up_plan, down_plan, length: int,
                          local_steps: int, mesh):
    """One scanned chunk of the client-sharded engine on ``mesh``, jitted
    with the state donated, as ``build_sharded_rollout_fn`` jits it."""
    return jax.jit(functools.partial(
        rollout_l2gd_sharded, mesh=mesh, grad_fn=grad_fn, steps=length,
        client_comp=up_plan, master_comp=down_plan, batch_axis=0,
        participation=None, local_steps=local_steps), donate_argnums=(1,))


def codec(c: dict):
    kw = {k: v for k, v in c.items() if k != "name"}
    return make_compressor(c["name"], **kw)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("c",))
def _program_readings(x0, x16, t, c: float):
    """From the program's own outputs after the first chunk: each
    leaf's change over the 14 local steps per client (the fresh and the
    cached round inverted: x16 = (1 - c)^2 x14 + c (2 - c) t), and the
    sums the target statistics need, of the target t against the mean
    of x14."""
    sq = jnp.zeros((), jnp.float32)
    mm = jnp.zeros((), jnp.float32)
    em = jnp.zeros((), jnp.float32)
    deltas = []
    for a0, a16, tt in zip(jax.tree_util.tree_leaves(x0),
                           jax.tree_util.tree_leaves(x16),
                           jax.tree_util.tree_leaves(t)):
        tt = tt.astype(jnp.float32)
        x14 = (a16.astype(jnp.float32) - c * (2.0 - c) * tt[None]) \
            / (1.0 - c) ** 2
        d = x14 - a0.astype(jnp.float32)
        deltas.append(jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim)))))
        m = jnp.mean(x14, axis=0)
        err = tt - m
        sq, mm, em = sq + jnp.sum(err * err), mm + jnp.sum(m * m), \
            em + jnp.sum(err * m)
    return jnp.stack(deltas, axis=1), sq, mm, em


def _stats(sq, mm, em):
    return float(np.sqrt(sq / mm)), float(em / np.sqrt(sq * mm))


class Job:
    """The cell's program objects for one seed: configuration, weights,
    traffic, plans, the client mesh of a multi-chip cell and the one
    jitted chunk."""

    def __init__(self, cell: dict, seed: int, roll=None):
        self.cell, self.seed = cell, int(seed)
        P = self.P = cell["params"]
        chips = cell["chips"]
        if P["clients"] % chips:
            raise ValueError(f"{P['clients']} clients do not divide over "
                             f"{chips} chips")
        self.mesh = make_client_mesh(chips) if chips > 1 else None
        self.model = harness.model_of(cell["config_spec"],
                                      cell["bench_dir"])
        self.cfg = self.model.program_config(cell["config_spec"])
        self.rspec = self.model.reference_spec(cell["config_spec"])
        self.n, self.chunk_len = P["clients"], P["chunk"]
        self.shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), self.cfg))
        self.d = sum(int(np.prod(s.shape))
                     for s in jax.tree_util.tree_leaves(self.shapes))
        self.tokens = TokenStream(self.n, self.cfg.vocab_size, P["batch"],
                                  P["seq"], seed=self.seed)
        self.pool = []        # step k's (clients, batch, seq) tokens
        self.hp = jax.tree_util.tree_map(jnp.asarray, L2GDHyper(
            eta=P["eta"], lam=P["lam"], p=P["p"], n=self.n))
        one = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                           self.shapes)
        self.up = make_plan(codec(P["uplink"]), one,
                            transport=P["transport"])
        self.down = make_plan(codec(P["downlink"]), one,
                              transport=P["transport"])
        build = program_chunk if self.mesh is None else functools.partial(
            program_sharded_chunk, mesh=self.mesh)
        self.roll = roll or build(program_grad_fn(self.cfg), self.up,
                                  self.down, self.chunk_len,
                                  P["local_steps"])
        self.key = jax.random.PRNGKey(P["protocol_key"])
        self.ledger = BitsLedger(self.n)
        self.up_bits, self.down_bits = self.up.round_bits(), \
            self.down.round_bits()
        self.xi_prev, self.done = 1, 0
        self.losses = []
        self.mismatches = 0
        self.host_ms = []     # per chunk: stack, dispatch, fetch, replay

    def batch_at(self, k: int):
        """Step k's tokens; every step's differ.  Made a chunk at a
        time, ahead of the window by ``fill``."""
        while len(self.pool) <= k:
            self.pool.extend(self.tokens.chunk_at(
                len(self.pool) // self.chunk_len, self.chunk_len))
        return self.pool[k]

    def fill(self, chunks: int) -> None:
        self.batch_at(chunks * self.chunk_len - 1)

    def device_of(self, client: int):
        """The device that holds ``client``."""
        if self.mesh is None:
            return jax.devices()[0]
        return self.mesh.devices.flat[client * self.mesh.size // self.n]

    def weights(self):
        """The seed's stacked weights, in the chunk's layout."""
        return weights.make_weights(self.seed, self.shapes, self.n,
                                    self.mesh)

    def stack(self, c0: int):
        """The chunk's (steps, clients, batch, seq) tokens on the
        device, or straight to their shards on a client mesh."""
        steps = [self.batch_at(c0 + i) for i in range(self.chunk_len)]
        if self.mesh is None:
            return {"tokens": jnp.stack([jnp.asarray(b) for b in steps])}
        batches = {"tokens": np.stack(steps)}
        return jax.device_put(batches, client_sharded_batch_shardings(
            self.mesh, batches))

    def chunk(self, state):
        """One chunk of the program's driver loop."""
        c0 = self.done
        ts = [time.perf_counter()]
        with harness.span("bench.stack"):
            batches = self.stack(c0)
        ts.append(time.perf_counter())
        with harness.span("bench.dispatch"):
            state, tr = self.roll(self.key, state, self.hp, batches, None)
        ts.append(time.perf_counter())
        with harness.span("bench.fetch"):
            xis = np.asarray(tr.xis)
            losses = np.asarray(tr.losses)
            counters = (int(tr.n_local), int(tr.n_agg_comm),
                        int(tr.n_agg_cached))
        ts.append(time.perf_counter())
        with harness.span("bench.replay"):
            # the driver's host work at a chunk boundary: per-step losses
            # to Python floats, counters, the ledger replay
            self.losses.extend((c0 + i, float(losses[i]))
                               for i in range(len(losses)))
            prevs = np.concatenate(([self.xi_prev], xis[:-1]))
            own = (int(np.sum(xis == 0)),
                   int(np.sum((xis == 1) & (prevs == 0))),
                   int(np.sum((xis == 1) & (prevs == 1))))
            self.mismatches += int(own != counters)
            self.xi_prev = self.ledger.replay_xi_trace(
                xis, self.up_bits, self.down_bits, xi_prev=self.xi_prev,
                start_step=c0)
        ts.append(time.perf_counter())
        self.host_ms.append(np.diff(ts) * 1e3)
        self.done += self.chunk_len
        return state, xis, losses, own

    def first_chunk(self):
        """Set-up's first chunk from the seed's weights, and the
        program-side readings of it (no reference yet).  A donated chunk
        consumes its state, so the readings take the weights made again
        from the seed."""
        state0 = init_state(self.weights())
        if self.mesh is not None:
            state0 = jax.device_put(
                state0, client_sharded_shardings(self.mesh, state0))
        state, xis, losses, _ = self.chunk(state0)
        del state0
        expected = np.array([0] * (self.chunk_len - 2) + [1, 1])
        self.mismatches += int(not np.array_equal(xis, expected))
        c = float(self.P["eta"] * self.P["lam"] / (self.n * self.P["p"]))
        deltas, sq, mm, em = _program_readings(self.weights(), state.params,
                                               state.cache, c)
        e, corr = _stats(float(sq), float(mm), float(em))
        return state, {"losses": np.asarray(losses[:-1], np.float64),
                       "deltas": np.asarray(deltas, np.float64),
                       "agg_err": e, "agg_corr": corr}


def _reference_step(loss, spec):
    @jax.jit
    def step(x, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(x, tokens, spec)
    return step


def _leaf_norms(tree):
    return np.array([float(jnp.linalg.norm(l.astype(jnp.float32)))
                     for l in jax.tree_util.tree_leaves(tree)])


def reference_readings(job: Job) -> dict:
    """The reference's first chunk: its 14 local steps per client from
    the same weights and batches, the loss before each of them and
    before the fresh round, each leaf's change and first gradient, and
    its own compressed target of its own mean.  Each client runs on the
    device that holds it in the program, from its own weights alone."""
    P, n, L = job.P, job.n, job.chunk_len
    f32_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), job.shapes)
    step = _reference_step(job.model.loss, job.rspec)
    lr = P["eta"] / (n * (1.0 - P["p"]))
    losses = np.zeros((L - 1, n))
    deltas, grads0, flats = [], [], []
    for i in range(n):
        dev = job.device_of(i)
        xi0 = weights.make_client_weights(job.seed, f32_shapes, i, dev)
        x = xi0
        for k in range(L - 1):
            loss, g = step(x, jax.device_put(job.batch_at(k)[i], dev))
            losses[k, i] = float(loss)
            if k == 0:
                grads0.append(_leaf_norms(g))
            if k < L - 2:
                x = jax.tree.map(lambda a, b: a - lr * b, x, g)
            del g
        deltas.append(_leaf_norms(jax.tree.map(lambda a, b: a - b, x, xi0)))
        flats.append(jnp.concatenate(
            [l.reshape(-1) for l in jax.tree_util.tree_leaves(x)]))
        del x, xi0
    m = ref_protocol.mean(flats)
    t = ref_protocol.target(
        jax.random.fold_in(weights.seed_key(job.seed), 0x7EF),
        flats, P["uplink"], P["downlink"])
    e, corr = ref_protocol.target_stats(t, m)
    return {"losses": losses.mean(axis=1), "deltas": np.stack(deltas),
            "grads0": np.stack(grads0), "agg_err": float(e),
            "agg_corr": float(corr)}


def compare(prog: dict, ref: dict, d: int) -> dict:
    """The numbers the cell's limits hold (see the module docstring);
    ``d`` is the number of elements of one client's model."""
    loss_gap = float(np.max(np.abs(prog["losses"] - ref["losses"])
                            / np.abs(ref["losses"])))
    gaps = []
    for i in range(ref["deltas"].shape[0]):
        g0 = ref["grads0"][i]
        keep = g0 >= 1e-3 * np.median(g0)
        dr, dp = ref["deltas"][i], prog["deltas"][i]
        scale = np.maximum(dr, np.median(dr[keep]))
        gaps.append(np.max((np.abs(dp - dr) / scale)[keep]))
    root_d = float(np.sqrt(d))
    return {"loss_gap": loss_gap, "update_gap": float(max(gaps)),
            "agg_err_z": abs(prog["agg_err"] - ref["agg_err"])
            / ref["agg_err"] * root_d,
            "agg_bias_z": abs(prog["agg_corr"]) * root_d}


def run(cell: dict, args, t0: float, device: dict, spec_b=None) -> tuple:
    """One run of a train cell: (result without checks, checks)."""
    enable_compile_cache()
    # every program, however quick to compile, is loaded from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    P = cell["params"]
    counter = harness.CompileCounter()
    marks = [("start", time.time())]
    job = Job(cell, args.seed)
    marks.append(("plans", time.time()))
    state, prog = job.first_chunk()
    marks.append(("weights, first chunk, readings", time.time()))
    t_warm = time.perf_counter()
    state, *_ = job.chunk(state)
    t_warm = time.perf_counter() - t_warm
    marks.append(("warm chunk", time.time()))
    # the window's batches, made ahead: twice the chunks the warm chunk's
    # pace gives (the window rarely reaches them; past them, a chunk's
    # batches are made inside it, a few milliseconds)
    job.fill(job.done // job.chunk_len
             + 2 * int(np.ceil(args.seconds / t_warm)) + 2)
    marks.append(("traffic", time.time()))
    print("set-up: imports and device check "
          f"{marks[0][1] - t0:.2f} s; " + "; ".join(
              f"{name} {t - marks[i][1]:.2f} s"
              for i, (name, t) in enumerate(marks[1:])), flush=True)

    trace_dir = os.path.join(harness.ROOT, "bench_out", "trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    steps = local = comm = bad = 0
    ends = []
    first = len(job.host_ms)
    with harness.profiled(bool(args.trace), trace_dir):
        counter.active = True
        t_start = time.time()
        with harness.span("bench.window"):
            while True:
                state, xis, losses, own = job.chunk(state)
                steps += len(xis)
                local, comm = local + own[0], comm + own[1]
                bad += int(np.sum(~np.isfinite(losses)))
                ends.append(time.time() - t_start)
                if ends[-1] >= args.seconds:
                    break
        t_end = time.time()
        counter.active = False
    devices = jax.devices()[:cell["chips"]]
    peak_mem = harness.memory_peak_bytes(devices)
    del state
    gc.collect()
    # the host's share of each chunk, to find a stall: the chunks' work
    # is the same in every run (the protocol key fixes it), so a chunk
    # that took longer than in other runs stalled in the phase shown
    host = np.array(job.host_ms[first:])
    print("host per chunk (ms, stack/dispatch/fetch/replay): median "
          f"{np.round(np.median(host, axis=0), 2).tolist()}, largest "
          f"{np.round(host.max(axis=0), 2).tolist()} in chunks "
          f"{host.argmax(axis=0).tolist()}", flush=True)

    window_s = t_end - t_start
    tokens = local * P["local_steps"] * job.n * P["batch"] * P["seq"]
    rec = {"kind": "train", "cell": cell["name"], "params": P,
           "chips": cell["chips"], "window_s": window_s, "steps": steps,
           "local_steps": local, "comm_rounds": comm, "tokens": tokens,
           "model_flops": tokens * job.model.train_flops_per_token(
               job.rspec, P["seq"]),
           "peaks": peaks.peaks_for(device["kind"]),
           "flat_size": job.d,
           "compiles_in_window": counter.count}
    print(f"window: {window_s:.3f} s, {steps} steps ({local} local, {comm} "
          f"communicated), compilations inside it: {counter.count} "
          f"{sorted(set(counter.names))}; chunk ends at "
          f"{[round(e, 3) for e in ends]} s", flush=True)

    out_device = dict(device, memory_peak_bytes=peak_mem)
    result = {"correct": True, "attempted": steps, "failed": bad,
              "device": out_device}
    spec_b = spec_b or harness.benchmark_spec()
    if args.trace:
        red = trace_lib.reduce(trace_lib.find_xplane(trace_dir))
        rec["trace"] = red
        out_device["busy_s"] = red["busy_s"]
        out_device["window_s"] = red["window_s"]
        metrics = {}
        for m in harness.metrics_of(spec_b, cell["name"], "per_layer"):
            v = harness.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"train_tokens_per_s": tokens / window_s / cell["chips"],
                  "setup_s": t_start - t0}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in harness.metrics_of(spec_b, cell["name"],
                                               "end_to_end")}
    result["metrics"] = metrics

    nums = compare(prog, reference_readings(job), job.d)
    limits = cell["limits"]
    # a cell's file lists the numbers it compares (a number whose
    # control and faults read no upper limit is not compared)
    checks = [harness.check(k, nums[k], limits[k]) for k in
              ("loss_gap", "update_gap", "agg_err_z", "agg_bias_z")
              if k in limits]
    checks.append(harness.check("protocol_mismatches", job.mismatches, 0,
                                kind="eq"))
    return result, checks
