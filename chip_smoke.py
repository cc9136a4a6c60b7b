"""Run both hot paths once on a TPU chip, at real size, and check the results.

    python chip_smoke.py               # one chip: phases a-d
    python chip_smoke.py --four-chips  # four chips: the sharded paths only

Every phase runs in this one process, and no phase catches its own failure:
a failed comparison or an exception exits non-zero.  The lines before the
last record each phase's compile seconds, run seconds, device memory and the
comparisons' worst errors; they are a smoke record, not a benchmark.  The
last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

One chip:
  a  device    platform, kind and count as JAX reports them; no TPU -> exit.
  b  engine    `pareto_search` over the 1,105,920-row network grid and
               `codesign_pareto` over 138,240 networks x 8 chiplet mixes.
               Each front is checked exactly against the chip's own metric
               columns, and its rows plus a strided sample are re-scored by
               the host's scalar float64 path.
  c  serving   zamba2-1.2b, 38 layers at published widths, random weights,
               through `ContinuousBatcher` with the Pallas kernels: 4
               requests of 1,024-token prompts and 32 new tokens on 4 slots.
               Prefill and every decode step's logits are compared with a
               float32, kernel-free, cache-free full forward.
  d  training  `Trainer`, 3 steps at zamba2-1.2b widths cut to one 7-layer
               period, photonic-MAC numerics through the compiled kernel;
               the step-0 loss against the float32 reference loss.

Four chips (``--four-chips``):
  `pareto_search(shard=True)` over the four chips against the same search on
  one chip, and `Trainer` on a 2x2 (data, model) mesh against the same three
  steps on one chip (reference ops: the kernels are not partitioned yet).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import json
import re
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.pareto_bench import (  # noqa: E402
    FULL_NET_AXES, PIPE_EXTRA_AXIS, TOPOLOGIES, _mix_library)
from repro import configs as C  # noqa: E402
from repro.core import CNN_WORKLOADS  # noqa: E402
from repro.core.accelerator import (  # noqa: E402
    AcceleratorConfig, evaluate_accelerator, evaluate_accelerator_grid)
from repro.core.power import engine_x64, evaluate_network  # noqa: E402
from repro.core.search import (  # noqa: E402
    ACCEL_OBJECTIVES, OBJECTIVES, codesign_pareto, front_is_exact,
    pareto_search)
from repro.core.sweep import (  # noqa: E402
    ChunkReducer, _as_f64, _config_sharding, _decode_program, _engine_kernel,
    _nets_program, _scenario_inputs, grid_spec, sweep_chunked)
from repro.data.pipeline import DataConfig  # noqa: E402
from repro.env import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim.adamw import OptConfig  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402
from repro.serve.engine import ContinuousBatcher  # noqa: E402

# --- tolerances, each with its reason -------------------------------------

# The chip has no float64 unit; XLA emulates float64 there with about 48
# significand bits, so the engine's metrics need not match the host's IEEE
# float64 bit for bit (on a v5e they agree to about 5e-14).  1e-9 leaves
# orders of magnitude of room for that rounding, and fails if any step ran
# in float32 (2**-24 ~ 6e-8) or a discrete planner decision (stage or
# subnetwork count) flipped.
ENGINE_RTOL = 1e-9

# Serving runs the model in float32, but on a v5e the kernels' float32 dots
# take one bfloat16 pass (each kernel alone misses a HIGHEST-precision
# reference by 3e-3 to 4e-3 of its largest output), and single-token decode
# runs the recurrence where the reference runs the chunked scan.  Logit
# errors are measured against the largest reference logit of the row; on
# a v5e they come to 6.3e-3 (2.7e-3 with the kernels off), while the same
# server in bfloat16 misses by 0.7 to 1.1.  1e-2 sits between.
SERVE_LOGIT_TOL = 1e-2

# Training runs the config's bfloat16 activations against the float32
# reference; the mean cross-entropy averages bfloat16 rounding (~4e-3 per
# value) over 8,192 tokens, so 1e-2 relative holds it, while a wrong
# quantization scale, mask or layer would move the loss by far more.
TRAIN_LOSS_RTOL = 1e-2

# The same bfloat16 step on a 2x2 mesh and on one chip differs only in
# reduction order (sharded matmuls, all-reduced gradients); AdamW then
# moves every weight by at most lr per step, so the three losses agree to
# well under the bfloat16 resolution of the loss itself.
MESH_LOSS_RTOL = 5e-3

ENGINE_CHUNK = 65536
CODESIGN_CHUNK = 9216          # divides the 138,240-network grid
SAMPLE_ROWS = 100

SERVE_REQUESTS, SERVE_SLOTS, PROMPT_LEN, NEW_TOKENS = 4, 4, 1024, 32
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3
SEED = 0

_KERNEL_RE = re.compile(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                        r'custom_call_target="tpu_custom_call"')


# --- bookkeeping ------------------------------------------------------------


class CompileClock:
    """Backend compile seconds, from JAX's own compile-duration events."""

    def __init__(self):
        self.secs, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.count += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    print(f"== phase {name}", flush=True)
    c0, n0, t0 = clock.secs, clock.count, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = clock.secs - c0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"{name}: wall {wall:.1f} s = compile {comp:.1f} s "
          f"({clock.count - n0} programs) + run {wall - comp:.1f} s; "
          f"device 0 bytes_in_use {stats.get('bytes_in_use')} "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)


def check(name: str, worst: float, tol: float, why: str) -> None:
    print(f"  check {name}: worst {worst:.3e} vs tol {tol:.0e} ({why}) "
          f"{'PASS' if worst <= tol else 'FAIL'}", flush=True)
    if not worst <= tol:
        raise AssertionError(f"{name}: worst {worst!r} exceeds {tol!r}")


def tpu_kernels(jitted, *args) -> collections.Counter:
    """Pallas kernels (`tpu_custom_call`) in the compiled program that
    `jitted` runs for `args`, by name."""
    text = jitted.lower(*args).compile().as_text()
    return collections.Counter(_KERNEL_RE.findall(text))


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def device_sets(tree) -> dict:
    """{number of devices an array spans: number of arrays}."""
    return dict(collections.Counter(
        len(x.sharding.device_set) for x in jax.tree.leaves(tree)))


# --- phase b: the design-space engine ----------------------------------------


class ColumnsReducer(ChunkReducer):
    """Keeps every row's objective values: the chip's own metric columns."""

    def __init__(self, objectives):
        self.objectives = tuple(objectives)

    def init(self, spec):
        return np.empty((spec.n, len(self.objectives)), np.float64)

    def step(self, carry, chunk):
        carry[chunk.start:chunk.stop] = np.stack(
            [chunk.metrics[k] for k in self.objectives], -1)
        return carry


def check_front(label: str, front, pts: np.ndarray) -> None:
    print(f"  {label}: {pts.shape[0]:,} points, front {front.size}")
    if not np.array_equal(pts[front.indices], front.points):
        raise AssertionError(f"{label}: front points differ from the chip's "
                             f"metric columns at the same rows")
    if not front_is_exact(front, pts):
        raise AssertionError(f"{label}: front is not the exact Pareto front "
                             f"of the chip's metric columns")
    print(f"  check {label}_front_exact: PASS (members mutually "
          f"non-dominated, every point dominated by or equal to a member)")


def rescore_rows(n: int, front) -> np.ndarray:
    """Every front row plus a strided sample of the grid."""
    return np.union1d(front.indices,
                      np.linspace(0, n - 1, SAMPLE_ROWS).astype(np.int64))


def network_phase(traffic, axes: dict, chunk: int) -> None:
    spec = grid_spec(TOPOLOGIES, **axes)
    front = pareto_search(traffic, topologies=TOPOLOGIES, chunk_size=chunk,
                          **axes)
    pts = sweep_chunked(traffic, ColumnsReducer(OBJECTIVES),
                        topologies=TOPOLOGIES, chunk_size=chunk, **axes)
    check_front("network", front, pts)
    rows = rescore_rows(spec.n, front)
    grid = spec.rows(rows)
    host = []
    for j in range(rows.size):
        net, d = grid.row_network(j)
        rep = evaluate_network(net, traffic, d)
        host.append([getattr(rep, k) for k in OBJECTIVES])
    print(f"  re-scored {rows.size} rows ({front.size} front + strided "
          f"sample) on the host's scalar path")
    check("network_vs_host", rel_err(pts[rows], np.array(host)),
          ENGINE_RTOL, "chip float64 is emulated")


def codesign_columns(wl, mixes, spec, step: int) -> np.ndarray:
    """(n_mix * n, 3) objective values of every joint point, from the same
    programs, at the same chunk shape, as `codesign_pareto`'s device path."""
    n = spec.n
    nets_prog = _nets_program(spec.topologies)
    decode = _decode_program(spec, step)
    pts = np.empty((len(mixes), n, len(ACCEL_OBJECTIVES)), np.float64)
    with engine_x64():
        tables = {k: _as_f64(v) for k, v in spec.axes.items()}
        base = {k: _as_f64(v) for k, v in spec.base.items()}
        for start in range(0, n, step):
            valid = min(step, n - start)
            cols, topo_id = decode(tables, base, np.int64(start))
            nets, mem_bw = nets_prog(cols, topo_id)
            out = evaluate_accelerator_grid(wl, mixes, nets, cols, mem_bw)
            pts[:, start:start + valid] = np.stack(
                [out[k][:, :valid] for k in ACCEL_OBJECTIVES], -1)
    return pts.reshape(-1, len(ACCEL_OBJECTIVES))


def codesign_phase(wl, mixes, axes: dict, step: int) -> None:
    front, spec = codesign_pareto(wl, mixes, topologies=TOPOLOGIES,
                                  chunk_size=step, **axes)
    pts = codesign_columns(wl, mixes, spec, step)
    check_front("codesign", front, pts)
    rows = rescore_rows(pts.shape[0], front)
    mix_id, local = np.divmod(rows, spec.n)
    grid = spec.rows(local)
    host = []
    for j in range(rows.size):
        net, d = grid.row_network(j)
        accel = AcceleratorConfig(
            name=f"row{rows[j]}", chiplets=list(mixes[mix_id[j]]),
            network=net,
            mem_bw_bytes_per_s=float(grid.cols["n_mem_chiplets"][j]
                                     * grid.cols["mem_bw_bytes_per_s"][j]),
            adaptive_gateways=True)
        rep = evaluate_accelerator(accel, wl, d)
        host.append([getattr(rep, k) for k in ACCEL_OBJECTIVES])
    print(f"  re-scored {rows.size} joint points ({front.size} front + "
          f"strided sample) on the host's scalar path")
    check("codesign_vs_host", rel_err(pts[rows], np.array(host)),
          ENGINE_RTOL, "chip float64 is emulated")


def engine_phase(net_axes: dict, mixes, chunk: int, cd_chunk: int) -> None:
    wl = CNN_WORKLOADS["ResNet18"]()
    network_phase(wl.traffic(), net_axes, chunk)
    codesign_phase(wl, mixes, {k: v for k, v in net_axes.items()
                               if k not in PIPE_EXTRA_AXIS}, cd_chunk)


# --- phase c: serving ---------------------------------------------------------


def init_params(cfg, seed: int):
    """Random weights from `seed`, made on the device in one program."""
    return jax.jit(lambda k: M.init(cfg, k)[0])(jax.random.PRNGKey(seed))


def serving_phase(cfg, n_requests: int, slots: int, prompt_len: int,
                  new_tokens: int, seed: int) -> None:
    test_cfg = dataclasses.replace(cfg, use_kernels=True, dtype="float32")
    ref_cfg = dataclasses.replace(cfg, use_kernels=False, dtype="float32")
    rng = np.random.default_rng(seed)
    prompts = rng.integers(2, cfg.vocab, size=(n_requests, prompt_len))
    with jax.default_matmul_precision("highest"):
        params = init_params(test_cfg, seed)
        eng = ContinuousBatcher(test_cfg, params, n_slots=slots,
                                max_len=prompt_len + new_tokens)
        # record what the server computes, at its own call sites
        prefill_logits, decode_logits = {}, []
        decode, prefill_fn = eng._decode, eng._prefill_fn

        def recording_prefill_fn(plen):
            # every slot is free at the start, so requests are prefilled in
            # submission order: the n-th prefill is request n
            fn = prefill_fn(plen)

            def call(p, toks):
                logits, cache = fn(p, toks)
                prefill_logits[len(prefill_logits)] = logits
                return logits, cache
            return call

        def recording_decode(p, cache, toks, pos):
            logits, cache = decode(p, cache, toks, pos)
            rids = [r.rid if r is not None else None for r in eng.slot_req]
            decode_logits.append((rids, logits))
            return logits, cache

        eng._prefill_fn, eng._decode = recording_prefill_fn, recording_decode
        for pr in prompts:
            eng.submit(pr.tolist(), new_tokens)
        t0 = time.perf_counter()
        done = sorted(eng.run(), key=lambda r: r.rid)
        # the recorders refer to the engine: unhook them, so that no
        # reference cycle keeps its weights on the device after this phase
        eng._prefill_fn, eng._decode = prefill_fn, decode
        print(f"  served {len(done)} requests x {new_tokens} tokens "
              f"through {slots} slots in {time.perf_counter() - t0:.1f} s "
              f"(compiles included)")
        assert [len(r.out) for r in done] == [new_tokens] * n_requests

        seqs = jnp.asarray([r.prompt + r.out[:-1] for r in done], jnp.int32)
        ref = jax.jit(lambda p, t: M.train_logits(ref_cfg, p, {"tokens": t}))
        ref_logits = np.asarray(ref(params, seqs))
        worst_prefill = max(
            _logit_err(prefill_logits[r][0, -1], ref_logits[r, prompt_len - 2])
            for r in range(n_requests))
        step_of = collections.Counter()
        worst_decode = 0.0
        for rids, logits in decode_logits:
            logits = np.asarray(logits)
            for s, rid in enumerate(rids):
                if rid is None:
                    continue
                t = prompt_len - 1 + step_of[rid]
                step_of[rid] += 1
                worst_decode = max(worst_decode,
                                   _logit_err(logits[s, -1], ref_logits[rid, t]))
        check("serve_prefill_logits", worst_prefill, SERVE_LOGIT_TOL,
              "float32 kernels vs float32 reference")
        check("serve_decode_logits", worst_decode, SERVE_LOGIT_TOL,
              f"{sum(step_of.values())} decode steps, float32 vs float32")

        plen = prompt_len - 1
        toks = jnp.zeros((1, plen), jnp.int32)
        kp = tpu_kernels(eng._prefills[plen], params, toks)
        kd = tpu_kernels(decode, params, eng.cache,
                         jnp.zeros((slots, 1), jnp.int32),
                         jnp.zeros((slots,), jnp.int32))
    print(f"  kernels in the prefill program: {dict(kp)}")
    print(f"  kernels in the decode program: {dict(kd)} (single-query "
          f"decode takes the cached path, which has no kernel)")
    for name in ("flash_attention", "ssm_scan"):
        if not kp[name]:
            raise AssertionError(f"prefill program holds no {name} kernel")


def _logit_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# --- phase d: training ----------------------------------------------------------


def train_config(cfg):
    """zamba2 widths, depth cut to one (mamba x 5, shared attention) period
    plus the remainder layer, photonic-MAC numerics through the kernels."""
    return dataclasses.replace(cfg, n_layers=cfg.hybrid_attn_every + 1,
                               use_photonic_mac=True, use_kernels=True)


def make_trainer(cfg, batch: int, seq: int, seed: int, ckpt: str, mesh=None):
    return Trainer(cfg, OptConfig(), DataConfig(global_batch=batch,
                                                seq_len=seq, seed=seed),
                   TrainerConfig(ckpt_dir=ckpt, seed=seed), mesh=mesh,
                   resume=False)


def training_phase(cfg, batch: int, seq: int, steps: int, seed: int) -> None:
    cfg = train_config(cfg)
    ref_cfg = dataclasses.replace(cfg, use_kernels=False, dtype="float32")
    with tempfile.TemporaryDirectory() as ckpt:
        tr = make_trainer(cfg, batch, seq, seed, ckpt)
        b0 = tr.source.batch_at(0)
        with jax.default_matmul_precision("highest"):
            ref_loss = float(jax.jit(
                lambda p, b: M.loss_fn(ref_cfg, p, b)[0])(tr.state.params, b0))
        t0 = time.perf_counter()
        tr.run(steps, quiet=True)
        print(f"  {steps} steps of {batch} x {seq} tokens in "
              f"{time.perf_counter() - t0:.1f} s (compile and final "
              f"checkpoint included)")
        losses = [h["loss"] for h in tr.history]
        print(f"  losses {losses}; float32 reference step-0 loss {ref_loss}")
        if len(losses) != steps or not np.all(np.isfinite(losses)):
            raise AssertionError(f"losses not all finite: {losses}")
        check("train_step0_loss", abs(losses[0] - ref_loss) / abs(ref_loss),
              TRAIN_LOSS_RTOL, "bfloat16 step vs float32 reference")
        kt = tpu_kernels(tr._step, tr.state, b0)
    print(f"  kernels in the train step program: {dict(kt)}")
    if not kt["photonic_mac"]:
        raise AssertionError("train step program holds no photonic_mac kernel")


# --- four chips ---------------------------------------------------------------


def sharded_engine_phase(axes: dict, chunk: int) -> None:
    traffic = CNN_WORKLOADS["ResNet18"]().traffic()
    t0 = time.perf_counter()
    f4 = pareto_search(traffic, topologies=TOPOLOGIES, chunk_size=chunk,
                       shard=True, **axes)
    t4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    f1 = pareto_search(traffic, topologies=TOPOLOGIES, chunk_size=chunk,
                       **axes)
    t1 = time.perf_counter() - t0
    print(f"  sharded search {t4:.1f} s, one-chip search {t1:.1f} s "
          f"(compiles included); fronts {f4.size} and {f1.size}")
    if not np.array_equal(f4.indices, f1.indices):
        raise AssertionError("sharded and one-chip fronts hold different rows")
    check("sharded_vs_one_chip_front", rel_err(f4.points, f1.points),
          ENGINE_RTOL, "same float64 program, partitioned")

    # one chunk laid out exactly as the sharded search lays it out
    spec = grid_spec(TOPOLOGIES, **axes)
    sharding = _config_sharding()
    with engine_x64():
        cols, topo_id = spec.chunk_cols(0, chunk)
        cols = {k: jax.device_put(_as_f64(v), sharding)
                for k, v in cols.items()}
        topo_id = jax.device_put(jnp.asarray(topo_id), sharding)
        bits, xfers = (_as_f64(traffic.total_bits),
                       _as_f64(traffic.n_transfers))
        print(f"  one chunk's columns span {device_sets(cols)} devices")
        nets, mets = _engine_kernel(spec.topologies)(
            cols, topo_id, _scenario_inputs(), bits, xfers, _as_f64(1.0))
        print(f"  its metrics span {device_sets(mets)} devices")


def mesh_training_phase(cfg, batch: int, seq: int, steps: int,
                        seed: int) -> None:
    # the reference ops on both sides: a Pallas (Mosaic) call inside the
    # GSPMD-partitioned step is refused ("Mosaic kernels cannot be
    # automatically partitioned"), and needs a shard_map wrapper first
    cfg = dataclasses.replace(train_config(cfg), use_kernels=False)
    losses = {}
    for label, mesh in (("2x2 mesh", make_test_mesh(data=2, model=2)),
                        ("one chip", None)):
        with tempfile.TemporaryDirectory() as ckpt:
            tr = make_trainer(cfg, batch, seq, seed, ckpt, mesh=mesh)
            print(f"  {label}: params span {device_sets(tr.state.params)} "
                  f"devices")
            tr.run(steps, quiet=True)
            losses[label] = [h["loss"] for h in tr.history]
            print(f"  {label}: losses {losses[label]}")
            del tr
    check("mesh_vs_one_chip_losses",
          rel_err(losses["2x2 mesh"], losses["one chip"]), MESH_LOSS_RTOL,
          "same bfloat16 steps, sharded")


# --- entry point ----------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine and trainer on four "
                         "chips, each against the same run on one chip")
    args = ap.parse_args()
    use_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    print(f"== phase a device: platform {dev.platform}, kind "
          f"{dev.device_kind!r}, count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"no TPU: JAX's first device is {dev.platform!r}")
    if args.four_chips and len(devices) < 4:
        sys.exit(f"--four-chips needs 4 devices, JAX sees {len(devices)}")

    clock = CompileClock()
    zamba = C.get("zamba2-1.2b")
    if args.four_chips:
        with phase("4-chip engine", clock):
            sharded_engine_phase(dict(FULL_NET_AXES, **PIPE_EXTRA_AXIS),
                                 ENGINE_CHUNK)
        with phase("4-chip training", clock):
            mesh_training_phase(zamba, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS,
                                SEED)
    else:
        with phase("b engine", clock):
            engine_phase(dict(FULL_NET_AXES, **PIPE_EXTRA_AXIS),
                         _mix_library(False), ENGINE_CHUNK, CODESIGN_CHUNK)
        with phase("c serving", clock):
            serving_phase(zamba, SERVE_REQUESTS, SERVE_SLOTS, PROMPT_LEN,
                          NEW_TOKENS, SEED)
        with phase("d training", clock):
            training_phase(zamba, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, SEED)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
