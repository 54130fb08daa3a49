"""The way back from an expert layer's sorted buffer to the tokens
(``ops/expert_combine.py``), on the CPU with the kernel interpreted: against
the gather of every assignment's row and the float32 sum it replaces, its
gradients against autodiff of that gather, the rows it reads, and the path
that the mesh a lowering sees chooses."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from katib_tpu.models.mla_moe import ROUTING, ExpertLayer, MlaMoeSizes, routing_counters
from katib_tpu.ops.expert_combine import Routes, combine, granule, plan_tile, way_back


def choose(routing: str, tokens: int, k: int, n_experts: int, rng) -> np.ndarray:
    """[T, k] distinct experts a token, the layer holding experts 0-7."""
    choices = np.stack([rng.permutation(n_experts)[:k] for _ in range(tokens)])
    if routing == "a-tile-holds-nothing":  # the first 256 tokens choose none of 0-7
        choices[:256] = np.stack([8 + rng.permutation(n_experts - 8)[:k] for _ in range(256)])
    elif routing == "every-choice-held":  # k of the 8 held, every token
        choices = np.stack([rng.permutation(8)[:k] for _ in range(tokens)])
    elif routing == "experts-left-empty":  # only 1, 4 and 6 of the held are chosen
        held = np.isin(choices, np.arange(8))
        choices = np.where(held, np.array([1, 4, 6])[choices % 3], choices)
        choices = np.stack([_distinct(row, n_experts, rng) for row in choices])
    elif routing == "one-expert-takes-every-token":
        choices[:, 0] = 3
        choices[:, 1:] = np.stack([8 + rng.permutation(n_experts - 8)[: k - 1] for _ in range(tokens)])
    return choices


def _distinct(row, n_experts, rng):
    """``row`` with repeated experts replaced by unheld ones it lacks."""
    out, seen = [], set()
    for e in row:
        while e in seen:
            e = 8 + rng.integers(n_experts - 8)
        seen.add(e)
        out.append(e)
    return np.array(out)


def sorted_buffer(choices, rows: int | None, width: int, dtype, seed: int = 0):
    """The buffer, the weights and the routes as ``_routed_rung`` makes them
    from ``choices`` (experts 0-7 held): assignments token-major, a stable
    sort by expert, unheld last; ``rows`` rows or the held ones, whichever
    is more, and those past the held ones stale (NaN)."""
    tokens, k = choices.shape
    held = (choices >= 0) & (choices < 8)
    key = np.where(held, choices, 8).reshape(-1)
    order = np.argsort(key, kind="stable")
    inverse = np.argsort(order)
    sizes = np.bincount(key[key < 8], minlength=8)
    n = int(sizes.sum())
    rows = max(rows or 1, n)
    pos = np.where(inverse < n, inverse, -1).reshape(tokens, k).T
    rng = np.random.default_rng(seed)
    buffer = rng.normal(size=(rows, width)).astype(np.float32)
    buffer[n:] = np.nan
    routes = Routes(
        jnp.asarray(pos, jnp.int32), jnp.asarray(np.where(pos >= 0, choices.T, -1), jnp.int32),
        jnp.asarray(order[:rows], jnp.int32), jnp.asarray(sizes, jnp.int32),
    )
    weights = jnp.asarray(rng.uniform(0.05, 1.5, size=(k, tokens)), jnp.float32)
    return weights, jnp.asarray(buffer, dtype), routes


def gathered(weights, rows, pos, dtype):
    """The expression the kernel replaced: every assignment's row, ``k x T``
    of them, each scaled and rounded to ``dtype``, summed over the choices in
    float32 and rounded once."""
    picked = rows[jnp.maximum(pos, 0)].astype(jnp.float32)
    terms = (weights[..., None] * picked).astype(dtype).astype(jnp.float32)
    return jnp.where(pos[..., None] >= 0, terms, 0.0).sum(axis=0).astype(dtype)


# (tokens, k, experts, width, the buffer's rows where they are more than the held)
ROUTINGS = {
    "balanced": (300, 6, 64, 256, 512),
    "a-tile-holds-nothing": (512, 4, 32, 128, 256),
    "every-choice-held": (200, 8, 8, 128, 1600),  # the full rung: every assignment held
    "experts-left-empty": (384, 6, 64, 128, 256),
    "one-expert-takes-every-token": (384, 3, 32, 128, 512),
    "width-2560": (256, 6, 64, 2560, 384),
}
# (rows' dtype, the result's, the weights: the forward's, the gradient to the
# tokens' (weights of one), a float32 model's)
MODES = {
    "forward": (jnp.float32, jnp.bfloat16, False),
    "backward": (jnp.bfloat16, jnp.bfloat16, True),
    "float32": (jnp.float32, jnp.float32, False),
}


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_sums_what_the_gather_did(routing, mode):
    """To the rounding of a k-term float32 sum: one unit in the last place of
    a bfloat16 result, and bit for bit where every token's choices are in
    the experts' order, the order in which the kernel's runs are staged; a
    float32 result within k roundings of the sum of the terms' magnitudes
    (the interpreter's product sums in an order of its own).  The stale rows
    past the held ones never reach a sum."""
    tokens, k, n_experts, width, rows = ROUTINGS[routing]
    rows_dtype, dtype, ones = MODES[mode]
    choices = choose(routing, tokens, k, n_experts, np.random.default_rng(3))
    for ordered in (True, False):
        picked = np.sort(choices, axis=1) if ordered else choices
        weights, buffer, routes = sorted_buffer(picked, rows, width, rows_dtype)
        if ones:
            weights = jnp.ones_like(weights)
        got, _ = way_back(weights, buffer, routes, dtype)
        assert got.shape == (tokens, width) and got.dtype == dtype
        got, want = (np.asarray(a, np.float32) for a in (got, gathered(weights, buffer, routes.pos, dtype)))
        assert np.isfinite(got).all()
        if dtype == jnp.float32:
            size = np.asarray(gathered(jnp.abs(weights), jnp.abs(buffer), routes.pos, dtype))
            np.testing.assert_array_less(np.abs(got - want), k * 2.0**-24 * size + 1e-30)
        elif ordered:
            np.testing.assert_array_equal(got, want)
        else:
            ulp = np.spacing(np.abs(want).astype(dtype)).astype(np.float32)
            np.testing.assert_array_less(np.abs(got - want), ulp + 1e-30)
        if routing == "a-tile-holds-nothing":
            assert not got[:256].any()


@pytest.mark.parametrize("routing", ["balanced", "every-choice-held", "a-tile-holds-nothing"])
def test_gradients_are_autodiffs_of_the_gather(routing):
    """``combine``'s rule against autodiff of the gather it replaced: to the
    weights (a row's inner product with its token's cotangent) and to the
    rows (a gather of C rows, scaled); zero on the stale rows."""
    tokens, k, n_experts, width, rows = ROUTINGS[routing]
    choices = choose(routing, tokens, k, n_experts, np.random.default_rng(5))
    weights, buffer, routes = sorted_buffer(choices, rows, width, jnp.float32)
    held = int(routes.sizes.sum())
    cot = jax.random.normal(jax.random.PRNGKey(2), (tokens, width), jnp.float32)

    def loss(fn):
        return lambda w, r: jnp.sum(fn(w, r).astype(jnp.float32) * cot)

    got = jax.grad(loss(lambda w, r: combine(w, r, routes, jnp.bfloat16)[0]), argnums=(0, 1))(weights, buffer)
    finite = buffer.at[held:].set(0.0)  # autodiff of the gather reads no stale row either
    want = jax.grad(loss(lambda w, r: gathered(w, r, routes.pos, jnp.bfloat16)), argnums=(0, 1))(weights, finite)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1][:held], want[1][:held])
    assert not np.asarray(got[1][held:]).any()


def test_the_counter_is_the_held_rows_and_the_granules_edges():
    """``moe_combine_rows`` on the step's counters: for every tile of tokens
    and held expert, the rows from its run's first granule to its last,
    counted here from the sorted positions themselves; at least the held
    rows, and under them plus two granules a run."""
    sizes = MlaMoeSizes(
        d_model=64, n_experts=32, experts_per_token=3, expert_width=32, n_shared_experts=0, experts_held=(0, 8)
    )
    tokens = 2 * 320
    logits = jax.random.normal(jax.random.PRNGKey(4), (2, 320, 32), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 320, 64), jnp.float32)
    layer = ExpertLayer(sizes, jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), h, logits)
    _, sown = layer.apply(params, h, logits, mutable=[ROUTING])
    counters = routing_counters(sown[ROUTING])

    _, top = jax.lax.top_k(np.asarray(logits).reshape(tokens, 32), 3)
    key = np.where(np.asarray(top) < 8, np.asarray(top), 8).reshape(-1)
    inverse = np.argsort(np.argsort(key, kind="stable"))
    g = granule(jnp.float32)  # the forward's rows
    tile = plan_tile(tokens, 3, 8, 64, jnp.float32)
    want = runs = 0
    for first in range(0, tokens, tile):
        assignments = np.arange(first * 3, min(first + tile, tokens) * 3)
        for e in range(8):
            at = inverse[assignments[key[assignments] == e]]
            if at.size:
                want += -(-(at.max() + 1) // g) * g - at.min() // g * g
                runs += 1
    held = counters["moe_assignments_held"]
    assert counters["moe_combine_rows"] == want
    assert held <= want < held + runs * 2 * g


@pytest.fixture()
def mesh():
    return Mesh(np.array(jax.devices()[:2]), ("data",))


@pytest.mark.parametrize(
    "where,kernel", [("one-device", True), ("gspmd", False), ("shard_map", True)],
)
def test_the_mesh_the_lowering_sees_chooses_the_path(mesh, where, kernel):
    """One device, or shard_map over every axis: the kernel, which counts the
    rows it copies.  Tokens that GSPMD shards over two devices: the gather
    of every assignment's row, counted as ``k x T``.  The same sums."""
    tokens, k, n_experts, width, rows = ROUTINGS["balanced"]
    choices = np.sort(choose("balanced", tokens, k, n_experts, np.random.default_rng(3)), axis=1)
    weights, buffer, routes = sorted_buffer(choices, rows, width, jnp.float32)
    fn = lambda w, r, routes: way_back(w, r, routes, jnp.bfloat16)  # noqa: E731
    if where == "gspmd":
        by_token, whole = (NamedSharding(mesh, PartitionSpec(*spec)) for spec in ((None, "data"), ()))
        weights, buffer = jax.device_put(weights, by_token), jax.device_put(buffer, whole)
        routes = Routes(*(jax.device_put(a, by_token if a.ndim == 2 else whole) for a in routes))
        out, copied = jax.jit(fn)(weights, buffer, routes)
    elif where == "shard_map":
        out, copied = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(), out_specs=PartitionSpec()))(
            weights, buffer, routes
        )
    else:
        out, copied = jax.jit(fn)(weights, buffer, routes)
    assert int(copied) < k * tokens if kernel else int(copied) == k * tokens
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(gathered(weights, buffer, routes.pos, jnp.bfloat16), np.float32)
    )


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one-device", "data-mesh"])
def test_the_trials_spans_say_what_the_way_back_read(tmp_path, on_mesh):
    """Through ``train_lm``: ``trial.init`` names the way back, every
    ``trial.eval`` carries ``moe_combine_rows`` beside ``moe_buffer_rows``:
    under the assignments on one device, all of them where a ``data`` axis of
    two devices runs the gather."""
    from katib_tpu.models import transformer
    from katib_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from katib_tpu.utils import tracing

    trial = {
        "block": "gqa_moe", "vocab_size": 64, "seq_len": 64, "n_seq": 40, "batch_size": 4, "steps": 2,
        "d_model": 48, "n_heads": 6, "n_kv_heads": 2, "head_dim": 8, "n_layers": 2, "window": 8,
        "window_layout": "01", "rope_layout": "01", "expert_width": 32, "n_experts": 8,
        "experts_per_token": 2, "experts_held_first": 2, "experts_held": 1,
    }
    mesh = make_mesh({DATA_AXIS: 2}, devices=jax.devices()[:2]) if on_mesh else None
    model = transformer._gqa_moe_model(dict(trial), 64, mesh)
    path = str(tmp_path / "trace.jsonl")
    tracer = tracing.Tracer(path)
    with tracing.use_tracer(tracer):
        transformer.train_lm(
            model, transformer.markov_dataset(64, 40, 64, seed=5), lr=1e-3, steps=2, batch_size=4,
            mesh=mesh, report_every=1, report=lambda step, loss, eval_loss: None,
        )
    tracer.close()
    records = list(tracing.read_journal(path))
    (init,) = [r["args"] for r in records if r["name"] == "trial.init"]
    assert init["expert_combine"] == ("every assignment, gather" if on_mesh else "held rows, kernel")
    evals = [r["args"] for r in records if r["name"] == "trial.eval"]
    assert len(evals) == 2
    for args in evals:
        total, held = args["moe_assignments_total"], args["moe_assignments_held"]
        if on_mesh:
            assert args["moe_combine_rows"] == total
        else:
            assert held <= args["moe_combine_rows"] < total
