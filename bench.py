"""Benchmark: DARTS supernet bilevel-search throughput on the local accelerator.

Times the flagship compute path — the second-order (unrolled + Hessian
correction) DARTS search step at the reference's CIFAR-10 configuration
(batch 64, 8 layers, 16 init channels; ``darts-cnn-cifar10/run_trial.py``) —
in THIS process and prints ONE JSON line.  It measures on a TPU or not at
all: with no TPU it exits non-zero.  The only CPU run is the labelled
rehearsal ``BENCH_SMALL=1 JAX_PLATFORMS=cpu python bench.py`` (tiny shapes,
``platform: "cpu"``, no MFU).  ROADMAP S0 replaces this file.

Reported numbers:
- ``value``: images/sec through the full bilevel step (arch + weight update);
- ``mfu``: model-FLOPs utilisation — XLA's own per-step flop count
  (``katib_tpu.costmodel`` CostRecord) divided by the chip's peak from
  the per-device-kind table (``katib_tpu/costmodel/peaks.py``); null on a
  device the table does not hold.

The persistent compilation cache goes where
``katib_tpu.runner.trial_runner.init_compile_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).

Env knobs:
  BENCH_SMALL=1           tiny shapes for the CPU rehearsal
  BENCH_WARM_ONLY=1       compile + one step only (cache priming), no timing
  BENCH_STEPS             timed steps (default 20, small: 3)
  BENCH_AMORTIZE_K        --amortize: cohort width the probe warms (default 4)
  BENCH_AMORTIZE_FRESH=1  --amortize: re-measure instead of using the memo
  BENCH_COHORT_K          --cohort mode: members per cohort (default 8)
  BENCH_COHORT_STEPS      --cohort mode: timed steps (default 200, small: 50)
  BENCH_COHORT_DEVICES    --cohort mode: devices on the trial axis (default 1;
                          the --cohort-devices N flag sets this plus the
                          virtual-device XLA flag for the child)

``python bench.py --cohort`` runs a separate measurement: serial vs
vmap-batched cohort trial throughput (``runner/cohort.py``) on a tiny
model where dispatch overhead dominates — the regime the cohort engine
optimizes.  Emits its own JSON line (serial/cohort trials-per-sec,
speedup) instead of the DARTS row.  ``--async-occupancy``, ``--pbt`` and
``--amortize`` (cold vs warm first step through the persistent cache) are
CPU measurements of the same kind, each in a child process.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

from katib_tpu.utils.booleans import parse_bool  # noqa: E402

_SMALL = parse_bool(os.environ.get("BENCH_SMALL"))
# batch is overridable for scaling studies: the supernet's convs are tiny
# (16-64 ch on 32x32), so per-op overhead dominates at the reference's
# batch 64 and throughput scales with batch until the MXU saturates
BATCH = int(os.environ.get("BENCH_BATCH", "8" if _SMALL else "64"))
NUM_LAYERS = 2 if _SMALL else 8
INIT_CHANNELS = 4 if _SMALL else 16
N_NODES = 2 if _SMALL else 4
WARMUP_STEPS = 1 if _SMALL else 2
TIMED_STEPS = max(1, int(os.environ.get("BENCH_STEPS", "3" if _SMALL else "20")))

# peak flops / HBM bandwidth come from the shared per-device-kind table
# (katib_tpu/costmodel/peaks.py)
_RESULT_TAG = "@@BENCH_RESULT@@"


def _device_barrier(jax_mod) -> None:
    """Stream barrier before a timer stops (lint code JAX105): device
    execution is in-order per stream, so blocking on a freshly enqueued
    trivial transfer implies every previously dispatched program retired.
    Complements the host-fetch integrity rule (see the 93x note in
    ``_child``) — used where the timed work leaves no value to fetch."""
    jax_mod.block_until_ready(jax_mod.device_put(0.0))


def _build_flagship(jax, jnp):
    """Build the full-size bilevel search step + inputs at the bench shapes.

    Shared by the timed child and the AOT compile-only child so the program
    that gets cost-analysed deviceless is byte-identical to the one that
    gets timed on the chip.
    """
    from katib_tpu.nas.darts.architect import (
        DartsHyper,
        init_search_state,
        make_search_step,
    )
    from katib_tpu.nas.darts.model import DartsNetwork, init_alphas
    from katib_tpu.nas.darts.ops import DEFAULT_PRIMITIVES
    from katib_tpu.parallel.train import cross_entropy_loss

    # remat off by default: at bench shapes the supernet fits HBM without
    # recompute, and the bilevel step's 5 gradient passes make recompute
    # expensive (the reference's torch trial does no remat either);
    # BENCH_REMAT=1 restores it for memory-constrained configs, and
    # BENCH_REMAT_POLICY=dots selects the matmul-saveable policy (keep
    # conv/matmul outputs, recompute only elementwise — the batch-scaling
    # configuration)
    remat = parse_bool(os.environ.get("BENCH_REMAT"))
    # BENCH_FUSED=1 evaluates the 4 depthwise-separable primitives through
    # the fused plan (2 masked depthwise + 2 batched pointwise per mixed op
    # instead of 6+6; nas/darts/fused.py) — the measured attack on the
    # small-op-bound 0.56% MFU profile
    fused = parse_bool(os.environ.get("BENCH_FUSED"))
    # BENCH_PAIRED_HESSIAN=1: the two finite-difference passes run as one
    # vmapped pass over stacked (w+, w-) — 4 sequential network passes per
    # bilevel step instead of 5 (architect.py DartsHyper.paired_hessian).
    # Math parity is f32-gated in tests; in bf16 the variants differ at
    # rounding level (the finite difference amplifies decorrelated
    # rounding), so this is an A/B-able throughput config, not a bitwise
    # twin.
    paired = parse_bool(os.environ.get("BENCH_PAIRED_HESSIAN"))
    net = DartsNetwork(
        primitives=DEFAULT_PRIMITIVES,
        init_channels=INIT_CHANNELS,
        num_layers=NUM_LAYERS,
        n_nodes=N_NODES,
        num_classes=10,
        remat_policy=os.environ.get("BENCH_REMAT_POLICY") or None,
        remat=remat,
        fused_convs=fused,
    )
    key = jax.random.PRNGKey(0)
    k_init, k_alpha, k_data = jax.random.split(key, 3)
    alphas = init_alphas(N_NODES, len(DEFAULT_PRIMITIVES), k_alpha)
    x = jax.random.normal(k_data, (BATCH, 32, 32, 3), jnp.float32)
    y = jax.random.randint(jax.random.fold_in(k_data, 1), (BATCH,), 0, 10)
    weights = net.init(k_init, x[:1], alphas)

    def loss_fn(w, a, batch):
        xb, yb = batch
        return cross_entropy_loss(net.apply(w, xb, a), yb)

    hyper = DartsHyper(
        total_steps=max(TIMED_STEPS, 1), unrolled=True, paired_hessian=paired
    )
    step = make_search_step(loss_fn, hyper, mesh=None)
    state = init_search_state(weights, alphas, hyper)
    return step, state, (x, y), net, remat


def _aot_child() -> None:
    """Compile the full-size bilevel step against a deviceless v5e
    topology (``jax.experimental.topologies``) and report the XLA cost +
    HBM analysis.  Needs no device: the pip ``libtpu`` compiles the
    program against the described v5e target.  Run it as
    ``JAX_PLATFORMS=cpu python bench.py --aot-child``.

    Emits: flops_per_step, HBM footprint (args+temps+code), whether it
    fits v5e's 16 GiB, and a roofline estimate — step time bounded below
    by max(compute at peak, bytes-accessed at peak HBM bandwidth), which
    yields an *upper bound* on achievable MFU for this program.
    """
    import jax
    import jax.numpy as jnp

    from katib_tpu.costmodel import aot as cm_aot
    from katib_tpu.costmodel import peaks as cm_peaks
    from katib_tpu.costmodel.record import CostRecord, cost_of_compiled

    t0 = time.perf_counter()
    dev = cm_aot.topology_device("v5e:1x1x1")
    topo_secs = time.perf_counter() - t0  # lint: unguarded-ok(deviceless AOT: topology lookup is host-only, no program dispatched)

    step, state, batch, net, remat = _build_flagship(jax, jnp)
    compiled, compile_secs = cm_aot.aot_compile(step, (state, batch, batch), dev)

    dtype_key = "bf16" if net.dtype == jnp.bfloat16 else "f32"
    rec = cost_of_compiled(compiled, program="bench.aot", dtype=dtype_key)
    if rec is None:  # cost analysis is backend-dependent; keep the report
        rec = CostRecord(program="bench.aot", dtype=dtype_key)
    peaks = cm_peaks.peaks_for("v5e")
    roof = rec.roofline(peaks)
    flops = rec.flops
    bytes_accessed = rec.bytes_accessed
    hbm_bytes = rec.hbm_bytes
    compute_secs = roof["compute_floor_step_secs"]
    memory_secs = roof["prefusion_bw_step_secs"]
    print(
        _RESULT_TAG
        + json.dumps(
            {
                "target": "v5e:1x1x1 (deviceless AOT, local libtpu)",
                "device_kind": getattr(dev, "device_kind", "?"),
                "flops_per_step": flops,
                "bytes_accessed": bytes_accessed,
                "hbm_bytes": hbm_bytes,
                "hbm_gib": round(hbm_bytes / 1024**3, 3),
                "hbm_fits_v5e": hbm_bytes < peaks.hbm_bytes,
                "dtype": dtype_key,
                # step-time band, not a point estimate: the compute floor
                # assumes MFU=1; the bandwidth figure charges XLA's
                # PRE-FUSION "bytes accessed" (every op's operands+results)
                # entirely to HBM, which overstates real traffic — the
                # measured step lands between the two
                "roofline": {
                    "compute_floor_step_secs": round(compute_secs, 6),
                    "compute_floor_img_per_sec": (
                        round(BATCH / compute_secs, 1) if compute_secs else None
                    ),
                    "prefusion_bw_step_secs": round(memory_secs, 6),
                    "prefusion_bw_img_per_sec": (
                        round(BATCH / memory_secs, 1) if memory_secs else None
                    ),
                },
                "compile_secs": round(compile_secs, 1),
                "topology_secs": round(topo_secs, 1),
                # single source with the memo-key derivation: a child
                # whose self-report drifted from _aot_expected_config would
                # silently mis-key the committed memos
                "config": _aot_expected_config(),
            }
        )
    )


def _memo_path(config: dict, stem: str) -> str:
    """Default config memoizes to the committed ``<stem>.json``;
    exploration configs (BENCH_BATCH / BENCH_REMAT / BENCH_REMAT_POLICY /
    BENCH_FUSED / BENCH_PAIRED_HESSIAN overrides) get their own file so a
    scaling study can never
    clobber the artifact the driver's end-of-round bench relies on.  One
    tag builder for BOTH the AOT and on-chip-capture memos, so the two
    can never key differently for the same config."""
    default = {
        "batch": 8 if config["small_shapes"] else 64,
        "num_layers": config["num_layers"],
        "init_channels": config["init_channels"],
        "small_shapes": config["small_shapes"],
        "remat": False,
    }
    if config == default:
        name = f"{stem}.json"
    else:
        tag = f"b{config['batch']}" + ("_remat" if config.get("remat") else "")
        if config.get("remat_policy"):
            tag += f"_{config['remat_policy']}"
        if config.get("fused"):
            tag += "_fused"
        if config.get("paired_hessian"):
            tag += "_pairhess"
        name = f"{stem}_{tag}.json"
    return os.path.join(_HERE, "artifacts", "flagship", name)


def _aot_memo_path(config: dict) -> str:
    return _memo_path(config, "aot_v5e")


def _aot_expected_config() -> dict:
    """The config block the current env would produce (must match the
    child's self-report for a memoized result to be valid)."""
    small = parse_bool(os.environ.get("BENCH_SMALL"))
    remat = parse_bool(os.environ.get("BENCH_REMAT"))
    cfg = {
        "batch": int(os.environ.get("BENCH_BATCH", "8" if small else "64")),
        "num_layers": 2 if small else 8,
        "init_channels": 4 if small else 16,
        "small_shapes": small,
        "remat": remat,
    }
    if os.environ.get("BENCH_REMAT_POLICY"):
        cfg["remat_policy"] = os.environ["BENCH_REMAT_POLICY"]
    if parse_bool(os.environ.get("BENCH_FUSED")):
        cfg["fused"] = True
    if parse_bool(os.environ.get("BENCH_PAIRED_HESSIAN")):
        cfg["paired_hessian"] = True
    return cfg


def _amortize_child() -> None:
    """Compile-amortization probe child: wire the persistent cache the
    parent points at, run the packaged mnist prewarm twin once (trace +
    compile + first dispatch), and report how long that took.  Run twice
    against one cache dir by ``_run_compile_amortization``, the second
    process pays deserialization instead of XLA — the fleet-amortization
    effect ``katib-tpu prewarm`` and the in-run worker bank on."""
    import jax

    from katib_tpu.compile.registry import REGISTRY
    from katib_tpu.models.mnist import mnist_prewarm
    from katib_tpu.runner.trial_runner import init_compile_cache

    init_compile_cache(os.environ.get("KATIB_COMPILE_CACHE"))
    shared = {
        "units": 16,
        "num_layers": 1,
        "n_train": 512,
        "n_test": 128,
        "batch_size": 64,
    }
    k = int(os.environ.get("BENCH_AMORTIZE_K", "4"))
    t0 = time.perf_counter()
    mnist_prewarm(shared, k, None)
    # prewarm's dummy step is dispatched async; without the barrier this
    # timer measured trace+compile+enqueue, not the executed first step
    _device_barrier(jax)
    first = time.perf_counter() - t0
    print(
        _RESULT_TAG
        + json.dumps(
            {
                "first_step_secs": round(first, 4),
                "registry_signatures": len(REGISTRY.signatures()),
            }
        )
    )


def _run_compile_amortization() -> dict | None:
    """Cold-vs-warm first-step measurement (parent side): two child
    processes share one fresh persistent-cache dir; the first compiles,
    the second deserializes.  Memoized like the AOT block (the number is a
    property of the toolchain, not the pool) in
    ``artifacts/flagship/compile_amortization.json``;
    ``BENCH_AMORTIZE_FRESH=1`` forces a re-measure."""
    import shutil

    expected = {
        "small_shapes": _SMALL,
        "k": int(os.environ.get("BENCH_AMORTIZE_K", "4")),
    }
    memo_path = os.path.join(
        _HERE, "artifacts", "flagship", "compile_amortization.json"
    )
    if not parse_bool(os.environ.get("BENCH_AMORTIZE_FRESH")):
        try:
            with open(memo_path) as f:
                memo = json.load(f)
            import jax as _jax

            if (
                memo.get("config") == expected
                and memo.get("jax_version") == _jax.__version__
            ):
                memo.setdefault("from_memo", True)
                return memo
        except (OSError, ValueError):
            pass
    env = dict(os.environ)
    # CPU children: the ratio measures the cache, not a device.  The
    # children's caches are placed by KATIB_COMPILE_CACHE below, which a
    # JAX_COMPILATION_CACHE_DIR from outside would outrank
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"

    def _phase(phase: str, env: dict) -> dict | None:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--amortize-child"],
                capture_output=True,
                text=True,
                env=env,
                timeout=float(os.environ.get("BENCH_AMORTIZE_TIMEOUT", "900")),
            )
        except subprocess.TimeoutExpired:
            print(
                f"bench: compile-amortization {phase} child timed out",
                file=sys.stderr,
            )
            return None
        block = None
        for line in (proc.stdout or "").splitlines():
            if line.startswith(_RESULT_TAG):
                try:
                    block = json.loads(line[len(_RESULT_TAG):])
                except json.JSONDecodeError:
                    continue
        if block is None:
            print(
                f"bench: compile-amortization {phase} child failed "
                f"rc={proc.returncode}:\n" + (proc.stderr or "")[-1500:],
                file=sys.stderr,
            )
        return block

    runs = []
    # fixed paths, emptied first: a cache directory that moves never hits
    root = os.path.join(_HERE, ".jax_cache", "bench-amortize")
    shutil.rmtree(root, ignore_errors=True)
    env["KATIB_COMPILE_CACHE"] = os.path.join(root, "cache")
    for phase in ("cold", "warm"):
        block = _phase(phase, env)
        if block is None:
            return None
        runs.append(block)
    cold = float(runs[0]["first_step_secs"])
    warm = float(runs[1]["first_step_secs"])
    result = {
        "config": expected,
        "cold_first_step_secs": cold,
        "warm_first_step_secs": warm,
        "speedup": round(cold / warm, 2) if warm > 0 else None,
        "platform": "cpu",
    }
    try:
        import jax as _jax

        result["jax_version"] = _jax.__version__
        os.makedirs(os.path.dirname(memo_path), exist_ok=True)
        with open(memo_path, "w") as f:
            json.dump(result, f, indent=2)
    except OSError:
        pass
    return result


def _child() -> None:
    """The measurement: init devices, build the full-size bilevel step,
    warm the compile cache, time it, print the result line.  Exits
    non-zero without a TPU; the one CPU run is the tiny rehearsal
    (``BENCH_SMALL=1 JAX_PLATFORMS=cpu``), labelled ``platform: "cpu"``."""
    import jax
    import jax.numpy as jnp

    from katib_tpu.runner.trial_runner import init_compile_cache

    t_init0 = time.perf_counter()
    devices = jax.devices()
    init_secs = time.perf_counter() - t_init0  # lint: unguarded-ok(client/runtime init timing: jax.devices() dispatches no program)
    platform = devices[0].platform
    rehearsal = _SMALL and os.environ.get("JAX_PLATFORMS") == "cpu"
    if platform != "tpu" and not rehearsal:
        print(
            f"bench: no TPU (platform {platform!r}); the only CPU run is "
            "BENCH_SMALL=1 JAX_PLATFORMS=cpu",
            file=sys.stderr,
        )
        sys.exit(2)
    init_compile_cache()

    step, state, batch, net, remat = _build_flagship(jax, jnp)

    # XLA's own flop count for one step (per-device); basis for MFU.
    # The jitted dispatch path is ALSO the timed path.
    from katib_tpu.costmodel.record import cost_of_compiled

    runner = jax.jit(step)
    # MFU numerator/denominator dtypes must match the COMPUTE dtype (the
    # supernet casts to its flax compute dtype internally — f32 inputs
    # still run bf16 matmuls)
    dtype_key = "bf16" if net.dtype == jnp.bfloat16 else "f32"
    cost_rec = None
    flops_per_step = 0.0
    compile_secs = 0.0
    try:
        lowered = runner.lower(state, batch, batch)
        t_c0 = time.perf_counter()
        compiled = lowered.compile()
        compile_secs = time.perf_counter() - t_c0  # lint: unguarded-ok(client-side compile is synchronous host work)
        cost_rec = cost_of_compiled(
            compiled, program="bench.step", dtype=dtype_key
        )
        flops_per_step = cost_rec.flops_per_step if cost_rec is not None else 0.0
    except Exception as e:  # cost analysis is backend-dependent
        print(f"bench: cost analysis unavailable ({e})", file=sys.stderr)

    # a tiny reduction whose result is FETCHED to the host ends the timed
    # section: real bytes computed on the chip cannot be faked by an
    # eagerly-resolved future (docs/performance.md, measurement integrity)
    @jax.jit
    def _redsum(s):
        return sum(
            jnp.sum(a.astype(jnp.float32)) for a in jax.tree_util.tree_leaves(s)
        )

    for _ in range(WARMUP_STEPS):
        state, metrics = runner(state, batch, batch)
    float(_redsum(metrics))  # warm the reducer too
    jax.block_until_ready(state)  # warmup fully retired before the clock starts

    if parse_bool(os.environ.get("BENCH_WARM_ONLY")):
        print(
            json.dumps(
                {
                    "warm_only": True,
                    "platform": platform,
                    "init_secs": round(init_secs, 1),
                    "compile_secs": round(compile_secs, 1),
                }
            )
        )
        return

    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, metrics = runner(state, batch, batch)
    float(_redsum(metrics))  # host fetch = the clock cannot stop early
    jax.block_until_ready(state)  # and the carry itself is retired (JAX105)
    dt = time.perf_counter() - t0

    img_per_sec = BATCH * TIMED_STEPS / dt
    step_secs = dt / TIMED_STEPS

    # fused-loop point: the SAME step folded lax.scan-style into one
    # dispatch per window (the search's default execution path since the
    # device-resident step loop flip) — the per-dispatch Python/transfer
    # overhead the eager numbers above pay per STEP is paid once per
    # WINDOW here, so (fused - eager) is the measured dispatch tax the
    # ROADMAP item-1 10x target collects on.  BENCH_STEP_LOOP_WINDOW
    # overrides the fold (default: TIMED_STEPS, one dispatch per timing).
    loop_window = max(
        1, int(os.environ.get("BENCH_STEP_LOOP_WINDOW", str(TIMED_STEPS)))
    )

    def _fused_loop(s, b):
        def body(c, _):
            c, m = step(c, b, b)
            return c, m["train_loss"]

        return jax.lax.scan(body, s, None, length=loop_window)

    loop_runner = jax.jit(_fused_loop, donate_argnums=(0,))
    t_lc0 = time.perf_counter()
    state, losses = loop_runner(state, batch)
    float(jnp.sum(losses))  # warm: trace+compile+first execution
    jax.block_until_ready(state)
    loop_compile_secs = time.perf_counter() - t_lc0
    loop_dispatches = max(1, TIMED_STEPS // loop_window)
    t_l0 = time.perf_counter()
    for _ in range(loop_dispatches):
        state, losses = loop_runner(state, batch)
    float(jnp.sum(losses))  # host fetch, same integrity rule as above
    jax.block_until_ready(state)  # donated carry retired before the clock stops
    loop_dt = time.perf_counter() - t_l0
    loop_steps = loop_window * loop_dispatches
    loop_img_per_sec = BATCH * loop_steps / loop_dt
    loop_step_secs = loop_dt / loop_steps
    from katib_tpu.costmodel.peaks import peaks_for

    # MFU needs the device's peak: none on the CPU rehearsal
    peaks = peaks_for() if platform == "tpu" else None

    def _mfu(secs):
        if cost_rec is None or peaks is None:
            return None
        return round(cost_rec.mfu(secs, peaks), 6)

    fused_note = (
        {
            "flops_note": (
                "cost-analysis flops for the fused plan count the masked "
                "grouped convs as if dense (13x the unfused program's "
                "count, aot_v5e_b64_fused.json vs aot_v5e.json) — compare "
                "plans by img/s, not MFU"
            )
        }
        if parse_bool(os.environ.get("BENCH_FUSED"))
        else {}
    )
    print(
        json.dumps(
            {
                "metric": "darts_bilevel_search_throughput",
                **fused_note,
                "value": round(float(img_per_sec), 2),
                "unit": "images/sec",
                "mfu": _mfu(step_secs),
                "dtype": dtype_key,
                "platform": platform,
                "step_secs": round(step_secs, 4),
                # the eager numbers above dispatch one step per host call
                "steps_per_dispatch": 1,
                "fused_loop": {
                    "metric": "darts_fused_loop_throughput",
                    "value": round(float(loop_img_per_sec), 2),
                    "unit": "images/sec",
                    "step_secs": round(loop_step_secs, 4),
                    "steps_per_dispatch": loop_window,
                    "dispatches": loop_dispatches,
                    "compile_secs": round(loop_compile_secs, 1),
                    "mfu": _mfu(loop_step_secs),
                },
                "flops_per_step": flops_per_step,
                "init_secs": round(init_secs, 1),
                "compile_secs": round(compile_secs, 1),
                # self-reported so recorded provenance can never drift from
                # what actually ran
                # single source with the memo-key derivation: a child
                # whose self-report drifted from _aot_expected_config would
                # silently mis-key the committed memos
                "config": _aot_expected_config(),
            }
        )
    )


def _cohort_child() -> None:
    """Measure serial vs vmap-cohort trial throughput (runner/cohort.py's
    optimization) in the regime it targets: per-step jitted dispatch of a
    tiny model, where Python/runtime dispatch — not FLOPs — bounds a sweep.
    K serial trials pay K×steps dispatches; one cohort pays steps dispatches
    of a [K]-batched program.  Prints one tagged JSON line with
    serial/cohort trials-per-sec and the speedup."""
    import jax
    import jax.numpy as jnp
    import optax

    from katib_tpu.parallel.mesh import (
        TRIAL_AXIS,
        make_mesh,
        padded_cohort_size,
        shard_members,
    )
    from katib_tpu.parallel.train import (
        TrainState,
        make_cohort_train_step,
        make_train_step,
        stack_pytrees,
    )

    platform = jax.devices()[0].platform

    k = int(os.environ.get("BENCH_COHORT_K", "8"))
    steps = int(os.environ.get("BENCH_COHORT_STEPS", "50" if _SMALL else "200"))
    devices = int(os.environ.get("BENCH_COHORT_DEVICES", "1"))
    mesh = None
    if devices > 1:
        devs = jax.devices()
        if len(devs) < devices:
            # a backend that ignores the forced-host-platform flag (a real
            # TPU) can't carve the trial axis; say so
            print(
                f"bench: only {len(devs)} devices for --cohort-devices "
                f"{devices}; measuring single-device cohort",
                file=sys.stderr,
            )
            devices = 1
        else:
            mesh = make_mesh({TRIAL_AXIS: devices}, devices=devs[:devices])
    dim, nbatch = 32, 256

    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (nbatch, dim), jnp.float32)
    y = jnp.sum(x, axis=1, keepdims=True)
    batch = (x, y)

    def loss_fn(params, b):
        xb, yb = b
        return jnp.mean((xb @ params["w"] + params["b"] - yb) ** 2)

    # same inject_hyperparams seam the mnist sweep uses: lr is a runtime
    # operand, so serial AND cohort each compile exactly one program
    tx = optax.inject_hyperparams(optax.sgd)(learning_rate=0.0)
    params = {
        "w": jax.random.normal(kw, (dim, 1), jnp.float32) * 0.01,
        "b": jnp.zeros((1,), jnp.float32),
    }
    lrs = [0.001 * (i + 1) for i in range(k)]

    def member_state(lr):
        # fresh buffers per member: the step donates its state input, and a
        # donated buffer shared with `params` would poison later members
        p = jax.tree_util.tree_map(jnp.array, params)
        s = TrainState.create(p, tx)
        hp = dict(s.opt_state.hyperparams)
        hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
        return s._replace(opt_state=s.opt_state._replace(hyperparams=hp))

    # ghost-pad the member dimension to fill the trial axis (k itself stays
    # the trials/sec denominator — ghosts are execution filler, not trials)
    k_exec = padded_cohort_size(k, mesh)
    exec_lrs = lrs + lrs[: k_exec - k]

    def cohort_state():
        s = stack_pytrees([TrainState.create(params, tx)] * k_exec)
        hp = dict(s.opt_state.hyperparams)
        hp["learning_rate"] = jnp.asarray(exec_lrs, jnp.float32)
        s = s._replace(opt_state=s.opt_state._replace(hyperparams=hp))
        return shard_members(s, mesh) if mesh is not None else s

    serial_step = make_train_step(loss_fn, tx)
    cohort_step = make_cohort_train_step(loss_fn, tx, mesh=mesh)
    cohort_batch = (
        jax.device_put(batch, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()
        ))
        if mesh is not None
        else batch
    )

    # warm both traces outside the clocks (steps donate their state input)
    s = member_state(0.01)
    for _ in range(3):
        s, _m = serial_step(s, batch)
    jax.block_until_ready(s)
    c = cohort_state()
    for _ in range(3):
        c, _m = cohort_step(c, cohort_batch)
    jax.block_until_ready(c)

    t0 = time.perf_counter()
    finals = []
    for lr in lrs:
        s = member_state(lr)
        for _ in range(steps):
            s, _m = serial_step(s, batch)
        finals.append(s)
    jax.block_until_ready(finals)
    t_serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    c = cohort_state()
    for _ in range(steps):
        c, _m = cohort_step(c, cohort_batch)
    jax.block_until_ready(c)
    t_cohort = time.perf_counter() - t0

    serial_tps = k / t_serial
    cohort_tps = k / t_cohort
    print(
        _RESULT_TAG
        + json.dumps(
            {
                "metric": "cohort_vmap_trial_throughput",
                "serial_trials_per_sec": round(serial_tps, 3),
                "cohort_trials_per_sec": round(cohort_tps, 3),
                "speedup": round(cohort_tps / serial_tps, 2),
                "k": k,
                "devices": devices,
                "members_per_device": k_exec // max(devices, 1),
                "steps": steps,
                "platform": platform,
            }
        )
    )


def _run_cohort() -> None:
    """Parent side of ``--cohort``: run the measurement in a child (CPU
    by default — this is a dispatch-overhead benchmark, not a chip
    benchmark) and print its JSON line.  ``--cohort-devices N`` shards the
    cohort's trial axis over N virtual CPU devices (the child gets the
    forced-host-platform flag), recording trials/sec vs device count."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if "--cohort-devices" in sys.argv:
        try:
            n = int(sys.argv[sys.argv.index("--cohort-devices") + 1])
        except (IndexError, ValueError):
            print("bench: --cohort-devices needs an integer", file=sys.stderr)
            sys.exit(2)
        env["BENCH_COHORT_DEVICES"] = str(n)
        flags = env.get("XLA_FLAGS", "")
        if n > 1 and "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--cohort-child"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
    )
    try:
        out, err = proc.communicate(timeout=900)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print("bench: cohort child timed out", file=sys.stderr)
        sys.exit(3)
    for line in (out or "").splitlines():
        if line.startswith(_RESULT_TAG):
            try:
                result = json.loads(line[len(_RESULT_TAG):])
            except json.JSONDecodeError:
                continue
            print(json.dumps(result))
            return
    print(
        f"bench: cohort child failed rc={proc.returncode}:\n" + (err or "")[-2000:],
        file=sys.stderr,
    )
    sys.exit(3)


def _async_occupancy_child() -> None:
    """Measure the async orchestrator (orchestrator/async_loops.py) against
    the synchronous loop in the regime it targets: a slow suggester (default
    0.5 s per call — a remote BO service or heavy acquisition optimizer)
    feeding short trials.  The sync loop pays the suggester on the dispatch
    critical path once per batch; the async loop banks ``suggestLookahead``
    proposals so the mesh never waits.  Prints one tagged JSON line with
    sync/async trials-per-sec, the speedup, and sustained occupancy."""
    import tempfile
    import time as _time

    from katib_tpu.core.types import (
        AlgorithmSpec,
        ExperimentSpec,
        FeasibleSpace,
        ObjectiveSpec,
        ObjectiveType,
        ParameterSpec,
        ParameterType,
    )
    from katib_tpu.orchestrator import Orchestrator
    from katib_tpu.orchestrator import orchestrator as orch_mod
    from katib_tpu.suggest.base import make_suggester as _real_make

    trials = int(os.environ.get("BENCH_ASYNC_TRIALS", "1000"))
    delay = float(os.environ.get("BENCH_ASYNC_SUGGEST_DELAY", "0.5"))
    train_secs = float(os.environ.get("BENCH_ASYNC_TRAIN_SECS", "0.2"))
    parallel = int(os.environ.get("BENCH_ASYNC_PARALLEL", "8"))

    def train_fn(ctx):
        _time.sleep(train_secs)
        ctx.report(step=1, loss=float(ctx.params["x"]) ** 2)

    class _Delayed:
        def __init__(self, inner):
            self.inner = inner
            self.adaptive = inner.adaptive
            self.spec = inner.spec
            self.calls = 0

        def get_suggestions(self, experiment, count):
            self.calls += 1
            _time.sleep(delay)
            return self.inner.get_suggestions(experiment, count)

    def sweep(mode: str) -> dict:
        spec = ExperimentSpec(
            name=f"bench-async-{mode}",
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="loss"
            ),
            algorithm=AlgorithmSpec(name="random", settings={"seed": "7"}),
            parameters=[
                ParameterSpec(
                    "x", ParameterType.DOUBLE, FeasibleSpace(min=-1.0, max=1.0)
                )
            ],
            train_fn=train_fn,
            parallel_trial_count=parallel,
            max_trial_count=trials,
            async_orch=(mode == "async"),
        )
        suggester_calls = []
        orig = orch_mod.make_suggester

        def delayed_make(s):
            d = _Delayed(_real_make(s))
            suggester_calls.append(d)
            return d

        with tempfile.TemporaryDirectory() as wd:
            orch_mod.make_suggester = delayed_make
            try:
                t0 = _time.perf_counter()
                orch = Orchestrator(workdir=wd)
                exp = orch.run(spec)
                # trials may have enqueued device work (here they sleep,
                # but the number must survive a real train_fn): quiesce
                # the stream before the clock stops
                import jax

                _device_barrier(jax)
                elapsed = _time.perf_counter() - t0
            finally:
                orch_mod.make_suggester = orig
        settled = sum(
            1 for t in exp.trials.values() if t.condition.is_terminal()
        )
        block = {
            "mode": mode,
            "trials": settled,
            "elapsed_secs": round(elapsed, 3),
            "trials_per_sec": round(settled / elapsed, 3),
            # slot-time actually spent training / slot-time available: an
            # apples-to-apples occupancy both loops can be scored on
            "derived_occupancy": round(
                settled * train_secs / (elapsed * parallel), 4
            ),
            "suggester_calls": suggester_calls[0].calls if suggester_calls else 0,
            "condition": exp.condition.value,
        }
        if orch.async_stats is not None:
            block["sustained_occupancy"] = orch.async_stats["sustained_occupancy"]
            block["lookahead"] = orch.async_stats["lookahead"]
            # supervision summary: a benched run that silently burned loop
            # restarts (or fell back to sync) is not a clean measurement
            block["loop_restarts"] = orch.async_stats["loop_restarts"]
            block["fallback"] = orch.async_stats["fallback"]
        return block

    sync = sweep("sync")
    async_ = sweep("async")
    result = {
        "benchmark": "async_occupancy",
        "platform": "cpu",
        "suggest_delay_secs": delay,
        "train_secs": train_secs,
        "parallel_trial_count": parallel,
        "sync": sync,
        "async": async_,
        "speedup": round(async_["trials_per_sec"] / sync["trials_per_sec"], 3),
        "note": (
            "dispatch-overhead benchmark on CPU: trials sleep "
            f"{train_secs}s, the suggester {delay}s/call; measures the "
            "control plane, not the chip"
        ),
    }
    print(_RESULT_TAG + json.dumps(result))


def _run_async_occupancy() -> None:
    """Parent side of ``--async-occupancy``: run the sync-vs-async sweep in
    a CPU child and print its JSON line."""
    env = dict(os.environ)
    env.pop("KATIB_ASYNC_ORCH", None)  # the spec flag drives each arm
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--async-occupancy-child"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
    )
    try:
        out, err = proc.communicate(timeout=1800)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print("bench: async-occupancy child timed out", file=sys.stderr)
        sys.exit(3)
    for line in (out or "").splitlines():
        if line.startswith(_RESULT_TAG):
            try:
                result = json.loads(line[len(_RESULT_TAG):])
            except json.JSONDecodeError:
                continue
            print(json.dumps(result))
            return
    print(
        f"bench: async-occupancy child failed rc={proc.returncode}:\n"
        + (err or "")[-2000:],
        file=sys.stderr,
    )
    sys.exit(3)


def _pbt_child() -> None:
    """Host-vs-on-device PBT A/B (parallel/pbt.py): the same digits
    workload evolved by the host ``pbt`` suggester (one orchestrator trial
    per member per generation, exploit = Orbax checkpoint copy) and by
    ``pbt-ondevice`` (the whole population as one stacked cohort, selection
    an on-device permutation inside the compiled generation step).  Equal
    training compute per arm: population × generations × steps SGD steps
    at the same batch.  Prints one tagged JSON line with generations/sec,
    population-img/sec, and the speedup."""
    import tempfile
    import time as _time

    from katib_tpu.core.types import (
        AlgorithmSpec,
        ExperimentSpec,
        FeasibleSpace,
        ObjectiveSpec,
        ObjectiveType,
        ParameterSpec,
        ParameterType,
    )
    from katib_tpu.models.pbt_digits import pbt_digits_trial
    from katib_tpu.orchestrator import Orchestrator

    population = int(os.environ.get("BENCH_PBT_POPULATION", "16"))
    generations = int(os.environ.get("BENCH_PBT_GENERATIONS", "10"))
    steps = int(os.environ.get("BENCH_PBT_STEPS", "300"))
    batch = 64  # pbt_digits default on both paths

    def host_train(ctx):
        # pin the per-round budget so both arms do identical training work
        ctx.params.setdefault("steps_per_round", steps)
        ctx.params.setdefault("batch", batch)
        pbt_digits_trial(ctx)

    def sweep(mode: str) -> dict:
        settings = {
            "n_population": str(population),
            "truncation_threshold": "0.25",
            "random_state": "7",
        }
        if mode == "ondevice":
            settings["generations"] = str(generations)
            settings["steps_per_generation"] = str(steps)
            algo, max_trials, train_fn = "pbt-ondevice", population, pbt_digits_trial
        else:
            # host turnover: one pool of `population` trials per generation
            algo, max_trials, train_fn = "pbt", population * generations, host_train
        with tempfile.TemporaryDirectory() as wd:
            if mode != "ondevice":
                settings["suggestion_trial_dir"] = os.path.join(wd, "lineage")
            spec = ExperimentSpec(
                name=f"bench-pbt-{mode}",
                objective=ObjectiveSpec(
                    type=ObjectiveType.MAXIMIZE, objective_metric_name="accuracy"
                ),
                algorithm=AlgorithmSpec(name=algo, settings=settings),
                parameters=[
                    ParameterSpec(
                        "lr", ParameterType.DOUBLE, FeasibleSpace(min=0.005, max=0.5)
                    )
                ],
                train_fn=train_fn,
                parallel_trial_count=population,
                max_trial_count=max_trials,
            )
            t0 = _time.perf_counter()
            exp = Orchestrator(workdir=wd).run(spec)
            import jax

            _device_barrier(jax)
            elapsed = _time.perf_counter() - t0
        settled = sum(1 for t in exp.trials.values() if t.condition.is_terminal())
        metric_name = spec.objective.objective_metric_name
        best = max(
            (
                m.value
                for t in exp.trials.values()
                if t.observation is not None
                for m in [t.observation.get(metric_name)]
                if m is not None
            ),
            default=None,
        )
        return {
            "mode": mode,
            "trials": settled,
            "generations": generations,
            "elapsed_secs": round(elapsed, 3),
            "generations_per_sec": round(generations / elapsed, 4),
            "population_imgs_per_sec": round(
                population * generations * steps * batch / elapsed, 1
            ),
            "best_accuracy": round(float(best), 4) if best is not None else None,
            "condition": exp.condition.value,
        }

    host = sweep("host")
    ondevice = sweep("ondevice")
    result = {
        "benchmark": "pbt_ondevice",
        "platform": "cpu",
        "population": population,
        "generations": generations,
        "steps_per_generation": steps,
        "batch": batch,
        "host": host,
        "ondevice": ondevice,
        "speedup": round(
            ondevice["generations_per_sec"] / host["generations_per_sec"], 3
        ),
        "note": (
            "same digits workload and per-member compute on CPU; host pays "
            "per-trial dispatch + Orbax checkpoint copies per generation, "
            "on-device runs the population as one compiled scan with "
            "selection as an in-program permutation"
        ),
    }
    print(_RESULT_TAG + json.dumps(result))


def _run_pbt() -> None:
    """Parent side of ``--pbt``: run the host-vs-on-device PBT A/B in a
    scrubbed-env CPU child and print its JSON line."""
    env = dict(os.environ)
    env.pop("KATIB_ASYNC_ORCH", None)
    env.pop("KATIB_PBT_ONDEVICE", None)  # the algorithm name drives each arm
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--pbt-child"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=env,
    )
    try:
        out, err = proc.communicate(timeout=1800)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        print("bench: pbt child timed out", file=sys.stderr)
        sys.exit(3)
    for line in (out or "").splitlines():
        if line.startswith(_RESULT_TAG):
            try:
                result = json.loads(line[len(_RESULT_TAG):])
            except json.JSONDecodeError:
                continue
            print(json.dumps(result))
            return
    print(
        f"bench: pbt child failed rc={proc.returncode}:\n" + (err or "")[-2000:],
        file=sys.stderr,
    )
    sys.exit(3)


def main() -> None:
    if "--aot-child" in sys.argv:
        _aot_child()
        return
    if "--amortize-child" in sys.argv:
        _amortize_child()
        return
    if "--cohort-child" in sys.argv:
        _cohort_child()
        return
    if "--cohort" in sys.argv:
        _run_cohort()
        return
    if "--async-occupancy-child" in sys.argv:
        _async_occupancy_child()
        return
    if "--async-occupancy" in sys.argv:
        _run_async_occupancy()
        return
    if "--pbt-child" in sys.argv:
        _pbt_child()
        return
    if "--pbt" in sys.argv:
        _run_pbt()
        return

    if "--amortize" in sys.argv:
        print(json.dumps(_run_compile_amortization()))
        return
    _child()


if __name__ == "__main__":
    main()
