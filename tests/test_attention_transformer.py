"""Flash-attention kernel, ring/ulysses sequence parallelism, and the
long-context transformer trial workload.

The Pallas kernels run in interpreter mode on the 8-device CPU platform
(conftest); numerics are checked against a dense jnp reference, mirroring
how the reference repo checks algorithm services against hand-built
requests (SURVEY.md §4 grpc_testing harness)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# interpreter-mode Pallas + sharded training loops: merge-gate tier
slow = pytest.mark.slow

from katib_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
    plan_tiles,
    reference_attention,
    reference_attention_with_lse,
)
from katib_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, make_mesh
from katib_tpu.parallel.ring_attention import make_sequence_parallel_attention


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), jnp.float32) for k in keys)


@slow
class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("blocks", [(32, 32), (32, 64), (64, 32)])
    def test_forward_matches_dense(self, causal, blocks):
        q, k, v = _qkv()
        bq, bk = blocks
        o, lse = flash_attention_with_lse(q, k, v, causal, None, bq, bk, None)
        o_ref, lse_ref = reference_attention_with_lse(q, k, v, causal)
        np.testing.assert_allclose(o, o_ref, atol=1e-5)
        np.testing.assert_allclose(lse, lse_ref, atol=1e-5)

    def test_gradients_match_dense(self):
        q, k, v = _qkv(s=32, d=8)

        def loss(f):
            def inner(q, k, v):
                return jnp.sum(jnp.sin(f(q, k, v)))

            return inner

        flash = loss(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16))
        dense = loss(lambda q, k, v: reference_attention(q, k, v, causal=True))
        gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=2e-5)

    @pytest.mark.parametrize("sq,sk", [(32, 64), (64, 32)])
    def test_causal_cross_length_matches_dense(self, sq, sk):
        """Bottom-right-aligned causal mask: kernel and dense reference must
        agree when seq_q != seq_k (ADVICE r1: the kernel was top-left)."""
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (2, 2, sq, 8), jnp.float32)
        k = jax.random.normal(kk, (2, 2, sk, 8), jnp.float32)
        v = jax.random.normal(kv, (2, 2, sk, 8), jnp.float32)
        o, lse = flash_attention_with_lse(q, k, v, True, None, 16, 16, None)
        o_ref, lse_ref = reference_attention_with_lse(q, k, v, True)
        np.testing.assert_allclose(o, o_ref, atol=1e-5)
        # rows with no visible keys: dense lse is a large-negative logsumexp
        # of mask values, kernel reports _MASK_VALUE; both merge as no-ops,
        # so only compare rows that attend to something
        vis = np.asarray(lse_ref) > -1e20
        np.testing.assert_allclose(
            np.asarray(lse)[vis], np.asarray(lse_ref)[vis], atol=1e-5
        )

        def loss(f):
            return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

        gf = jax.grad(
            loss(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16)),
            argnums=(0, 1, 2),
        )(q, k, v)
        gd = jax.grad(
            loss(lambda q, k, v: reference_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=2e-5)

    def test_lse_cotangent_flows(self):
        """The logsumexp output is differentiable — required for ring
        attention's merge to backprop correctly."""
        q, k, v = _qkv(s=32, d=8)

        def f(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, True, None, 16, 16, None)
            return jnp.sum(o * o) + jnp.sum(jnp.cos(lse))

        def g(q, k, v):
            o, lse = reference_attention_with_lse(q, k, v, True)
            return jnp.sum(o * o) + jnp.sum(jnp.cos(lse))

        gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=2e-5)


# (seq_q, seq_k, d_k, d_v, causal, block_q, block_k[, key-value heads of the two
# query heads, window]).  The causal square has eight k tiles to a q tile: q
# tile 1 (rows 64-127) runs k tiles 0-3 (0 and 1 wholly visible, 2 and 3
# crossed by the diagonal) and skips 4-7.
KERNEL_CASES = {
    "causal-square": (256, 256, 16, 16, True, 64, 32),
    "q-shorter": (128, 256, 16, 16, True, 64, 32),
    "q-longer": (256, 128, 16, 16, True, 32, 64),
    "narrow-values": (128, 128, 48, 32, True, 32, 32),
    "full": (128, 128, 16, 16, False, 64, 32),
    # both query heads read one key-value head: its dk and dv sum the two
    "grouped": (128, 128, 16, 16, True, 32, 32, 1),
    # the 40 newest keys: q tile 3 (rows 96-127) walks k tiles 1-3 and skips 0
    "window": (128, 128, 16, 16, True, 32, 32, 2, 40),
    "grouped-window-q-shorter": (64, 128, 16, 32, True, 32, 32, 1, 40),
}


def _case(name):
    """A case of ``KERNEL_CASES`` whole: key-value heads 2 and no window
    where it names none."""
    case = KERNEL_CASES[name]
    return case + (2, None)[len(case) - 7 :]


def _f32(x):
    return x.astype(jnp.float32)


def _kernel_inputs(sq, sk, d_k, d_v, dtype, seed=5, h_kv=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, 2, sq, d_k), dtype)
    k = jax.random.normal(ks[1], (1, h_kv, sk, d_k), dtype)
    v = jax.random.normal(ks[2], (1, h_kv, sk, d_v), dtype)
    w_o = jax.random.normal(ks[3], (1, 2, sq, d_v), jnp.float32)
    w_lse = jax.random.normal(ks[4], (1, 2, sq), jnp.float32)
    return q, k, v, w_o, w_lse


def _outputs_and_grads(attn, q, k, v, w_o, w_lse):
    """Output, log-sum-exp, and dq, dk, dv of a loss that weighs both (the
    ``lse`` cotangent flows); rows that see no key leave the loss."""

    def loss(q, k, v):
        o, lse = attn(q, k, v)
        seen = lse > -1e20
        return jnp.sum(_f32(o) * w_o) + jnp.sum(jnp.where(seen, lse, 0.0) * w_lse), (o, lse)

    (_, (o, lse)), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (o, lse) + grads


def _kernel_tolerances(dtype, want):
    """float32 operands: 1e-5, as the kernel always agreed with the dense
    reference.  bfloat16 operands: ``p`` and ``dS`` are rounded to 8 bits
    before the second product (each moves by at most 2**-8 of itself) and the
    result is rounded once more on the way out (2**-8 of itself): a sum of such
    terms stays within 2**-7 of the largest value, and 2**-6 leaves a margin of
    two.  The log-sum-exp never sees a rounded ``p``: its products of bfloat16
    operands are exact in float32."""
    if dtype == jnp.float32:
        return [1e-5] * 5
    top = [float(jnp.max(jnp.abs(_f32(w)))) for w in want]
    return [2.0**-6 * top[0], 1e-5 * max(top[1], 1.0)] + [2.0**-6 * t for t in top[2:]]


class TestKernelNumerics:
    """Tier-1: the kernels in interpret mode against the dense reference
    computed in float32 from the same inputs.  At these sizes the backward is
    the single walk (``one_walk``); dq + dkv are called directly."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_matches_dense_in_float32(self, case, dtype):
        sq, sk, d_k, d_v, causal, bq, bk, h_kv, window = _case(case)
        q, k, v, w_o, w_lse = _kernel_inputs(sq, sk, d_k, d_v, dtype, h_kv=h_kv)
        grad = lambda q, k, v: _outputs_and_grads(  # noqa: E731
            lambda q, k, v: flash_attention_with_lse(q, k, v, causal, None, bq, bk, True, window),
            q, k, v, w_o, w_lse,
        )
        # forward and the one walk, the latter over (batch, key-value head, its query heads, q tiles)
        assert f"grid=(1, {h_kv}, {2 // h_kv}, {sq // bq})" in str(jax.make_jaxpr(grad)(q, k, v))
        got = grad(q, k, v)
        want = _outputs_and_grads(
            lambda q, k, v: reference_attention_with_lse(q, k, v, causal, None, window),
            _f32(q), _f32(k), _f32(v), w_o, w_lse,
        )
        assert got[0].dtype == got[2].dtype == dtype and got[1].dtype == jnp.float32
        assert got[0].shape == (1, 2, sq, d_v) and got[3].shape == k.shape and got[4].shape == v.shape
        seen = np.asarray(want[1]) > -1e20
        if sq > sk:  # rows before the diagonal: output 0, log-sum-exp the mask value
            assert not seen[..., : sq - sk].any() and seen[..., sq - sk :].all()
            np.testing.assert_array_equal(_f32(got[0])[..., : sq - sk, :], 0.0)
            np.testing.assert_array_equal(got[1][..., : sq - sk], want[1][..., : sq - sk])
        for name, g, w, tol in zip(("o", "lse", "dq", "dk", "dv"), got, want, _kernel_tolerances(dtype, want)):
            np.testing.assert_allclose(_f32(g), w, rtol=0, atol=tol, err_msg=name)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_one_walk_equals_dq_and_dkv(self, case, dtype):
        """The two backwards on the same operands (a cotangent on the
        log-sum-exp among them): both round the same ``p`` and ``dS``, so they
        differ by the order of float32 sums, and by the last bit of a
        bfloat16 result where that tips its rounding."""
        from katib_tpu.ops import flash_attention as fa

        sq, sk, d_k, d_v, causal, bq, bk, h_kv, window = _case(case)
        q, k, v, w_o, w_lse = _kernel_inputs(sq, sk, d_k, d_v, dtype, h_kv=h_kv)
        assert fa.one_walk(sq, sk, d_k, d_v, dtype, bq, bk)
        o, lse = flash_attention_with_lse(q, k, v, causal, None, bq, bk, True, window)
        do = w_o.astype(dtype)
        dmd = jnp.sum(_f32(do) * _f32(o), axis=-1) - w_lse  # as _bwd: delta less the lse's cotangent
        operands = (q, k, v, do, lse[:, :, None, :], dmd[:, :, None, :])
        kernel = dict(sm_scale=d_k**-0.5, causal=causal, shift=sk - sq, window=window)
        walk = fa._bwd_walk(operands, kernel, bq, bk, True)
        split = fa._bwd_split(operands, kernel, bq, bk, True)
        for name, g, w, like in zip(("dq", "dk", "dv"), walk, split, (q, k, v)):
            assert g.shape == w.shape == like.shape and g.dtype == w.dtype == dtype
            top = float(jnp.max(jnp.abs(_f32(w))))
            tol = 1e-5 if dtype == jnp.float32 else 2.0**-8 * top
            np.testing.assert_allclose(_f32(g), _f32(w), rtol=0, atol=tol, err_msg=name)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    def test_planned_tiles_equal_explicit_128(self, dtype):
        q, k, v, w_o, w_lse = _kernel_inputs(256, 256, 32, 32, dtype)
        assert plan_tiles(256, 256, 32, 32, dtype) == (256, 256)
        planned, explicit = (
            _outputs_and_grads(
                lambda q, k, v: flash_attention_with_lse(q, k, v, True, None, bq, bk, True),
                q, k, v, w_o, w_lse,
            )
            for bq, bk in ((None, None), (128, 128))
        )
        # other tiles sum the same terms in another order (and round p
        # against another running maximum): equal to the rounding above
        for g, w, tol in zip(planned, explicit, _kernel_tolerances(dtype, explicit)):
            np.testing.assert_allclose(_f32(g), _f32(w), rtol=0, atol=tol)


# the shapes that run the kernel: the benchmark's two cells, gpt2-medium
# (a configuration file, no cell), and the ring path's chunks
# (scripts/run_longcontext_tpu.py: 4096 positions over 4 chips, float32 in the
# slow tests); (seq_q, seq_k, d_k, d_v, dtype)
PLANNED_SHAPES = {
    "gpt2-small": (1024, 1024, 64, 64, jnp.bfloat16),
    "kanana-2-30b-a3b-ep8": (4096, 4096, 192, 128, jnp.bfloat16),
    "gpt2-medium": (1024, 1024, 64, 64, jnp.bfloat16),
    "ring-chunk-bf16": (1024, 1024, 64, 64, jnp.bfloat16),
    "ring-chunk-f32": (1024, 1024, 64, 64, jnp.float32),
    "long-context": (16384, 16384, 128, 128, jnp.bfloat16),
    "short": (64, 64, 16, 16, jnp.float32),
    "odd-length": (1000, 1000, 64, 64, jnp.bfloat16),
}


class TestTilePlan:
    @pytest.mark.parametrize("name", sorted(PLANNED_SHAPES))
    def test_tiles_divide_align_and_fit(self, name):
        from katib_tpu.ops import flash_attention as fa

        sq, sk, d_k, d_v, dtype = PLANNED_SHAPES[name]
        bq, bk = plan_tiles(sq, sk, d_k, d_v, dtype)
        assert sq % bq == 0 and sk % bk == 0
        # a block's last two dimensions divide the dtype's tile or are whole:
        # rows by 8 (float32) or 16 (bfloat16), the statistics' lanes by 128
        sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
        assert bq == sq or (bq % 128 == 0 and bq % sublanes == 0)
        assert bk == sk or bk % sublanes == 0
        # the tiles are planned for the kernels that stream them; what runs
        # (the single walk wherever its accumulators fit) is within the limit
        assert fa.vmem_bytes(sq, sk, d_k, d_v, dtype, bq, bk, "dq+dkv") <= fa.VMEM_BUDGET_BYTES
        runs = "walk" if fa.one_walk(sq, sk, d_k, d_v, dtype, bq, bk) else "dq+dkv"
        assert fa.vmem_bytes(sq, sk, d_k, d_v, dtype, bq, bk, runs) <= fa.VMEM_LIMIT_BYTES == 2 * fa.VMEM_BUDGET_BYTES
        if min(sq, sk) >= 1024 and sq % 128 == 0:
            assert bq >= 256 and bk >= 128  # more rows streamed per operand loaded than the old 128

    @pytest.mark.parametrize(
        "shape,mib",
        [
            ((1024, 1024, 64, 64), 7.9), ((4096, 4096, 192, 128), 23.7), ((4096, 4096, 128, 128), 17.1),
            ((16384, 16384, 128, 128), 53.1),
        ],
        ids=["gpt2-small", "kanana-2-30b-a3b-ep8", "ouro-2.6b-l6", "smallthinker-21b-a3b-ep8"],
    )
    def test_the_cells_take_the_single_walk(self, shape, mib):
        """A head's K, V, dk, dv and the two float32 accumulators beside the
        score tiles: inside the budget at three cells' shapes, inside the
        limit at the fourth's 16384 keys."""
        from katib_tpu.ops import flash_attention as fa

        tiles = plan_tiles(*shape, jnp.bfloat16)
        assert tiles == (512, 512) and fa.one_walk(*shape, jnp.bfloat16, *tiles)
        walk = fa.vmem_bytes(*shape, jnp.bfloat16, *tiles, "walk")
        assert walk / 2**20 == pytest.approx(mib, abs=0.05)
        assert walk <= (fa.VMEM_BUDGET_BYTES if shape[0] <= 4096 else fa.VMEM_LIMIT_BYTES)
        assert walk > fa.vmem_bytes(*shape, jnp.bfloat16, *tiles, "dq+dkv")

    @pytest.mark.parametrize("seq,backward", [(16384, "one walk"), (32768, "dq+dkv")])
    def test_the_shapes_decide_which_backward_runs(self, seq, backward):
        """Twice the keys are twice the accumulators: over the limit, and
        ``_bwd`` lowers to dq and dkv as it did (shapes only: nothing runs)."""
        from katib_tpu.ops import flash_attention as fa

        shape = (seq, seq, 128, 128, jnp.bfloat16)
        tiles = plan_tiles(*shape)
        walks = fa.one_walk(*shape, *tiles)
        assert walks == (backward == "one walk")
        assert (fa.vmem_bytes(*shape, *tiles, "walk") <= fa.VMEM_LIMIT_BYTES) == walks
        qkv = [jax.ShapeDtypeStruct((1, h, seq, 128), jnp.bfloat16) for h in (4, 2, 2)]
        grad = jax.grad(lambda q, k, v: flash_attention(q, k, v, interpret=True).astype(jnp.float32).sum(), (0, 1, 2))
        jaxpr = str(jax.make_jaxpr(grad)(*qkv))
        n_q, n_k = seq // tiles[0], seq // tiles[1]
        grids = {"forward": f"grid=(1, 4, {n_q})", "walk": f"grid=(1, 2, 2, {n_q})", "dkv": f"grid=(1, 2, {n_k}, 2)"}
        counts = {name: jaxpr.count(grid) for name, grid in grids.items()}
        assert counts == ({"forward": 1, "walk": 1, "dkv": 0} if walks else {"forward": 2, "walk": 0, "dkv": 1})

    def test_explicit_tiles_pass_through(self):
        q, k, v, _, _ = _kernel_inputs(256, 256, 16, 16, jnp.float32)
        jaxpr = str(jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, block_q=64, block_k=32, interpret=True))(q, k, v))
        assert "grid=(1, 2, 4)" in jaxpr  # 256 rows in q tiles of 64
        # and a tile longer than the sequence is the sequence
        o = flash_attention(q[:, :, :32], k[:, :, :32], v[:, :, :32], block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(o, reference_attention(q[:, :, :32], k[:, :, :32], v[:, :, :32]), atol=1e-5)

    @pytest.mark.parametrize("blocks", [(48, 32), (32, 48), (None, 96)], ids=str)
    def test_tile_that_does_not_divide_raises(self, blocks):
        q, k, v, _, _ = _kernel_inputs(128, 128, 16, 16, jnp.float32)
        with pytest.raises(ValueError, match="must divide sequence lengths"):
            flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1], interpret=True)


@slow
class TestSequenceParallelAttention:
    @pytest.mark.parametrize("strategy", ["ring", "ulysses"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, strategy, causal):
        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        q, k, v = _qkv(b=4, h=4, s=64, d=16, seed=1)
        attn = make_sequence_parallel_attention(mesh, strategy=strategy, causal=causal)
        o = jax.jit(attn)(q, k, v)
        o_ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(o, o_ref, atol=1e-4)

    def test_ring_gradient_matches_dense(self):
        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        q, k, v = _qkv(b=2, h=2, s=32, d=8, seed=2)
        attn = make_sequence_parallel_attention(mesh, strategy="ring", causal=True)

        def loss(q, k, v):
            return jnp.sum(jnp.sin(attn(q, k, v)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(reference_attention(q, k, v, causal=True)))

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_seq_axis_of_one_degenerates_to_single_chip(self):
        mesh = make_mesh({DATA_AXIS: 8, SEQ_AXIS: 1})
        q, k, v = _qkv(b=8, h=2, s=32, d=8)
        attn = make_sequence_parallel_attention(mesh, strategy="ring", causal=True)
        np.testing.assert_allclose(
            attn(q, k, v), reference_attention(q, k, v, causal=True), atol=1e-5
        )


@slow
class TestTransformerLM:
    def test_training_reduces_loss_on_sharded_mesh(self):
        from katib_tpu.models.transformer import (
            TransformerLM,
            make_attention_fn,
            markov_dataset,
            train_lm,
        )

        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        model = TransformerLM(
            vocab_size=64, d_model=64, n_heads=4, n_layers=2, max_seq_len=128,
            attn_fn=make_attention_fn(mesh, strategy="ring"),
        )
        data = markov_dataset(64, 256, 128, seed=0)
        losses = []
        final = train_lm(
            model, data, lr=3e-3, steps=30, batch_size=16, mesh=mesh,
            report=lambda step, loss, eval_loss: losses.append(loss),
        )
        assert losses[-1] < losses[0] - 0.5
        assert np.isfinite(final)

    def test_transformer_trial_via_orchestrator(self):
        """End-to-end: random search over the long-context LM workload —
        best objective exists and completed == max_trial_count (the e2e
        invariants from the reference's run-e2e-experiment.py:52-60)."""
        from katib_tpu.core.types import (
            AlgorithmSpec,
            ExperimentCondition,
            ExperimentSpec,
            FeasibleSpace,
            ObjectiveSpec,
            ObjectiveType,
            ParameterSpec,
            ParameterType,
        )
        from katib_tpu.models.transformer import transformer_trial
        from katib_tpu.orchestrator import Orchestrator

        # tiny fixed workload knobs ride along as degenerate search dims
        fixed = [
            ParameterSpec("steps", ParameterType.INT, FeasibleSpace(min=8, max=8)),
            ParameterSpec("d_model", ParameterType.INT, FeasibleSpace(min=32, max=32)),
            ParameterSpec("seq_len", ParameterType.INT, FeasibleSpace(min=64, max=64)),
            ParameterSpec("n_seq", ParameterType.INT, FeasibleSpace(min=64, max=64)),
            ParameterSpec("batch_size", ParameterType.INT, FeasibleSpace(min=8, max=8)),
        ]
        spec = ExperimentSpec(
            name="tlm-random",
            algorithm=AlgorithmSpec(name="random"),
            objective=ObjectiveSpec(
                type=ObjectiveType.MINIMIZE, objective_metric_name="eval_loss"
            ),
            parameters=[
                ParameterSpec("lr", ParameterType.DOUBLE, FeasibleSpace(min=1e-3, max=1e-2)),
                *fixed,
            ],
            max_trial_count=2,
            parallel_trial_count=1,
            train_fn=transformer_trial,
        )
        exp = Orchestrator().run(spec)
        assert exp.condition is ExperimentCondition.MAX_TRIALS_REACHED
        assert exp.completed_count == 2
        assert exp.optimal is not None


# -- a trial's programs, built once a process (tier-1: tiny, dense, CPU) ------

TINY = dict(vocab_size=32, d_model=16, n_heads=2, max_seq_len=16)


def _tiny_lm(n_layers=2, mesh=None, **fields):
    from katib_tpu.models.transformer import TransformerLM, make_attention_fn

    fields.setdefault("attn_fn", make_attention_fn(mesh))
    return TransformerLM(n_layers=n_layers, **{**TINY, **fields})


def _tiny_data(seed=0):
    from katib_tpu.models.transformer import markov_dataset

    return markov_dataset(TINY["vocab_size"], 48, TINY["max_seq_len"], seed=seed)


def _traced_train_lm(tmp_path, name, model, **kwargs):
    """``train_lm`` inside a ``train_fn`` span, as the trial runner calls it;
    returns the reported losses and the journal's records by span name."""
    from katib_tpu.models.transformer import train_lm
    from katib_tpu.utils import tracing

    path = str(tmp_path / f"{name}.jsonl")
    tracer = tracing.Tracer(path)
    losses = []
    with tracing.use_tracer(tracer), tracing.span("train_fn", trial=name) as sp:
        for counter in tracing.JIT_COUNTERS:  # as runner/trial_runner.py opens it
            sp.add(counter, 0)
        train_lm(
            model, _tiny_data(), batch_size=4,
            report=lambda step, loss, eval_loss: losses.append((loss, eval_loss)), **kwargs,
        )
    tracer.close()
    return losses, {r["name"]: r["args"] for r in tracing.read_journal(path)}


class TestTrialPrograms:
    @pytest.mark.parametrize(
        "second",
        [
            dict(lr=3e-3, steps=6, warmup_frac=0.1),
            dict(lr=1e-3, steps=9, warmup_frac=0.1),
            dict(lr=1e-3, steps=6, warmup_frac=0.5),
            dict(lr=1e-4, steps=3, warmup_frac=0.4),
        ],
        ids=["lr", "steps", "warmup_frac", "all"],
    )
    def test_equal_structure_reuses_programs(self, tmp_path, second):
        # dropout 0.05 is this test's own structure: whatever ran before in
        # the process, the first call here is the one that builds at most
        _traced_train_lm(tmp_path, "a", _tiny_lm(dropout=0.05), lr=1e-3, steps=6, warmup_frac=0.1)
        losses, spans = _traced_train_lm(tmp_path, "b", _tiny_lm(dropout=0.05), **second)
        assert spans["trial.init"]["programs"] == "reused"
        assert spans["train_fn"]["jit_programs"] == 0
        assert spans["train_fn"]["jit_trace_s"] == spans["train_fn"]["jit_lower_s"] == 0
        assert len(losses) >= 2 and np.all(np.isfinite(losses))

    def test_trial_init_names_what_attention_runs(self, tmp_path):
        from katib_tpu.models import transformer

        _, spans = _traced_train_lm(tmp_path, "a", _tiny_lm(dropout=0.05), lr=1e-3, steps=2)
        assert spans["trial.init"]["attn_tiles"] == "dense"  # no kernel on the CPU
        # on the chip make_attention_fn gives the kernel: the dtype and the
        # tiles it plans for the benchmark's shapes
        small = transformer.TransformerLM(
            vocab_size=50257, d_model=768, n_heads=12, attn_fn=transformer._flash_causal_attention
        )
        assert transformer.attn_tiles(small, 1024) == "bfloat16 q512 k512, backward one walk"
        assert plan_tiles(1024, 1024, 64, 64, jnp.bfloat16) == (512, 512)
        from katib_tpu.models.mla_moe import MlaMoeLM, MlaMoeSizes

        latent = MlaMoeLM(
            vocab_size=64,
            sizes=MlaMoeSizes(n_heads=32, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
            attn_fn=transformer._flash_causal_attention,
        )
        assert transformer.attn_tiles(latent, 4096) == "bfloat16 q%d k%d, backward one walk" % plan_tiles(4096, 4096, 192, 128, jnp.bfloat16)
        # a head whose accumulators pass the kernel's VMEM limit keeps the two kernels, and says so
        assert transformer.attn_tiles(latent, 32768) == "bfloat16 q%d k%d, backward dq+dkv" % plan_tiles(32768, 32768, 192, 128, jnp.bfloat16)
        assert transformer.attn_tiles(dataclasses.replace(small, attn_fn=lambda q, k, v: q), 1024) == "seq-parallel"

    def test_trial_init_names_how_the_loss_runs(self, tmp_path):
        """One device: the head's product inside the loss, in chunks sized
        from the shapes; over a mesh: the loss on the dense logits."""
        from katib_tpu.models import transformer

        _, spans = _traced_train_lm(tmp_path, "a", _tiny_lm(dropout=0.05), lr=1e-3, steps=2)
        assert spans["trial.init"]["loss"] == "fused rows=4 x 1"
        small = transformer.TransformerLM(vocab_size=50257, d_model=768, n_heads=12)
        assert transformer.loss_path(small, 8, 1024, None) == "fused rows=4 x 2"
        mesh = make_mesh({DATA_AXIS: 4, SEQ_AXIS: 1}, devices=jax.devices()[:4])
        assert transformer.loss_path(small, 8, 1024, mesh) == "fused"
        losses, spans = _traced_train_lm(
            tmp_path, "m", _tiny_lm(mesh=mesh, dropout=0.05), lr=1e-3, steps=2, mesh=mesh
        )
        assert spans["trial.init"]["loss"] == "fused" and np.all(np.isfinite(losses))

    def test_new_depth_builds_and_trains_its_own(self, tmp_path):
        from katib_tpu.models import transformer

        _traced_train_lm(tmp_path, "a", _tiny_lm(2, dropout=0.07), lr=1e-3, steps=3)
        _, spans = _traced_train_lm(tmp_path, "b", _tiny_lm(3, dropout=0.07), lr=1e-3, steps=3)
        assert spans["trial.init"]["programs"] == "built"
        assert spans["train_fn"]["jit_programs"] >= 3  # init, step_fn, eval_fn
        blocks = {}
        for depth in (2, 3):
            programs, reused = transformer._programs_for(_tiny_lm(depth, dropout=0.07), 1.0, None)
            assert reused
            state = jax.eval_shape(lambda k: programs.init(k, 16), jax.random.PRNGKey(0))
            blocks[depth] = sorted(k for k in state.params["params"] if k.startswith("Block_"))
            # AdamW's two moments, shaped as the parameters
            assert jax.tree_util.tree_structure(state.opt_state[0].mu) == jax.tree_util.tree_structure(state.params)
        assert blocks == {2: ["Block_0", "Block_1"], 3: ["Block_0", "Block_1", "Block_2"]}

    def test_same_arguments_same_attention(self):
        from katib_tpu.models.transformer import make_attention_fn

        assert make_attention_fn() is make_attention_fn()
        mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        again = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        assert make_attention_fn(mesh, "ring") is make_attention_fn(again, strategy="ring")
        assert make_attention_fn(mesh, "ring") is not make_attention_fn(mesh, "ulysses")
        assert _tiny_lm(mesh=mesh) == _tiny_lm(mesh=again)
        assert hash(_tiny_lm(mesh=mesh)) == hash(_tiny_lm(mesh=again))

    @pytest.mark.parametrize("attention", ["of_the_mesh", "dense"])
    def test_two_meshes_do_not_share_an_entry(self, attention):
        from katib_tpu.models import transformer

        a = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})
        b = make_mesh({DATA_AXIS: 4, SEQ_AXIS: 2})

        def model(mesh):
            return _tiny_lm(mesh=mesh) if attention == "of_the_mesh" else _tiny_lm(attn_fn=None)

        on_a, _ = transformer._programs_for(model(a), 1.0, a)
        on_b, _ = transformer._programs_for(model(b), 1.0, b)
        on_none, _ = transformer._programs_for(model(None), 1.0, None)
        assert on_a is not on_b and on_a is not on_none and on_b is not on_none
        assert transformer._programs_for(model(a), 1.0, a) == (on_a, True)
        # the state comes out replicated over its own mesh
        state = on_b.init(jax.random.PRNGKey(0), 16)
        assert all(x.sharding.mesh == b for x in jax.tree_util.tree_leaves(state))
        assert all(x.sharding.is_fully_replicated for x in jax.tree_util.tree_leaves(state))

    def test_grad_clip_is_part_of_the_key(self):
        from katib_tpu.models import transformer

        one, _ = transformer._programs_for(_tiny_lm(), 1.0, None)
        half, _ = transformer._programs_for(_tiny_lm(), 0.5, None)
        assert one is not half
        assert transformer._programs_for(_tiny_lm(), 1, None)[0] is one

    def test_table_is_bounded_least_recently_used_goes(self, monkeypatch):
        from collections import OrderedDict

        from katib_tpu.models import transformer

        monkeypatch.setattr(transformer, "_PROGRAMS", OrderedDict())
        widths = [8 * (i + 1) for i in range(transformer._PROGRAMS_MAX + 1)]
        first = [transformer._programs_for(_tiny_lm(max_seq_len=w), 1.0, None)[0] for w in widths[:-1]]
        # the oldest entry is used again, so the second oldest goes
        assert transformer._programs_for(_tiny_lm(max_seq_len=widths[0]), 1.0, None) == (first[0], True)
        transformer._programs_for(_tiny_lm(max_seq_len=widths[-1]), 1.0, None)
        assert len(transformer._PROGRAMS) == transformer._PROGRAMS_MAX
        assert transformer._programs_for(_tiny_lm(max_seq_len=widths[0]), 1.0, None) == (first[0], True)
        assert transformer._programs_for(_tiny_lm(max_seq_len=widths[1]), 1.0, None)[1] is False

    def test_threads_get_one_whole_entry(self, monkeypatch):
        """More threads than cores ask for one new structure at once: every
        one is handed the same complete entry, and one of them built it."""
        import sys
        import threading
        from collections import OrderedDict

        from katib_tpu.models import transformer

        monkeypatch.setattr(transformer, "_PROGRAMS", OrderedDict())
        n = 32
        got = [None] * n
        start = threading.Barrier(n)

        def ask(i):
            start.wait(timeout=30)
            got[i] = transformer._programs_for(_tiny_lm(), 1.0, None)

        threads = [threading.Thread(target=ask, args=(i,)) for i in range(n)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert all(g is not None and g[0] is got[0][0] for g in got)
        assert all(callable(f) for f in got[0][0])
        assert sorted(reused for _, reused in got) == [False] + [True] * (n - 1)


def _plain_lm_loss(logits, tokens):
    """Next-token cross entropy as autodiff sees it written down: float32
    ``log_softmax`` over the vocabulary, the targets' log-probabilities, their
    mean over the ``B x (S - 1)`` positions that have a next token."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0])


# -- the cross entropy as one operation with its own backward ----------------


def _head_case(dtype, bias=True, shift=0.0, vocab=131, batch=4):
    """Hidden states, kernel, bias and tokens at a vocabulary that is no
    multiple of 128; ``shift`` moves every logit (through the bias)."""
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(k[0], (batch, 9, 16)).astype(dtype)
    w = jax.random.normal(k[1], (16, vocab))
    b = 2.0 * jax.random.normal(k[2], (vocab,)) + shift if bias else None
    tokens = jax.random.randint(k[3], (batch, 9), 0, vocab)
    return x, w, b, tokens


def _plain_head_loss(x, w, b, tokens):
    logits = jnp.dot(x.astype(jnp.float32), w)
    return _plain_lm_loss(logits if b is None else logits + b, tokens)


def _results_of_shape(jaxpr, shape, dtype=jnp.float32):
    """Every equation result of one shape and dtype, sub-programs included,
    and the primitives' names."""
    n, names = 0, set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if "name" in eqn.params:
            names.add(str(eqn.params["name"]))
        n += sum(getattr(v.aval, "shape", None) == shape and v.aval.dtype == dtype for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            m, more = _results_of_shape(sub, shape, dtype)
            n, names = n + m, names | more
    return n, names


class TestLmLoss:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("shift", [0.0, 80.0, -80.0])
    def test_dense_logits_equal_the_plain_formula(self, shift, dtype):
        from katib_tpu.models.transformer import lm_loss

        x, w, b, tokens = _head_case(jnp.float32, shift=shift)
        logits = (jnp.dot(x, w) + b).astype(dtype)
        got, d_got = jax.value_and_grad(lm_loss)(logits, tokens)
        want, d_want = jax.value_and_grad(_plain_lm_loss)(logits, tokens)
        assert got.dtype == jnp.float32 and d_got.dtype == dtype
        np.testing.assert_allclose(got, want, rtol=1e-6)
        tol = 1e-7 if dtype == jnp.float32 else 2e-4  # one bfloat16 rounding of a gradient below 1/32
        np.testing.assert_allclose(d_got.astype(jnp.float32), d_want.astype(jnp.float32), rtol=2e-5, atol=tol)
        assert np.all(d_got[:, -1] == 0)  # the last position has no next token

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
    @pytest.mark.parametrize("shift", [0.0, 80.0, -80.0])
    def test_head_inside_equals_the_plain_formula(self, shift, bias, dtype):
        """What the model hands over with ``multiply_head=False``: value, dx,
        dW and db against the product multiplied out and the plain formula."""
        from katib_tpu.models.lm_head import HeadInputs
        from katib_tpu.models.transformer import lm_loss

        x, w, b, tokens = _head_case(dtype, bias, shift)
        args = (0, 1, 2) if bias else (0, 1)
        got, d_got = jax.value_and_grad(lambda x, w, b: lm_loss(HeadInputs(x, w, b), tokens), args)(x, w, b)
        want, d_want = jax.value_and_grad(_plain_head_loss, args)(x, w, b, tokens)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        for a, c in zip(d_got, d_want):
            assert a.dtype == c.dtype and a.shape == c.shape
            rtol = 2e-5 if a.dtype == jnp.float32 else 2**-7  # dx in the hidden states' dtype: one rounding
            np.testing.assert_allclose(a.astype(jnp.float32), c.astype(jnp.float32), rtol=rtol, atol=1e-6)

    @pytest.mark.parametrize("rows", [2, 1])
    @pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
    def test_several_chunks_equal_one(self, bias, rows):
        from katib_tpu.models.lm_head import HeadInputs, head_loss

        x, w, b, tokens = _head_case(jnp.bfloat16, bias)
        args = (0, 1, 2) if bias else (0, 1)

        def grads(rows):
            # a cotangent other than 1: the backward scales, then rounds dx once
            return jax.jit(jax.value_and_grad(lambda x, w, b: 3.0 * head_loss(HeadInputs(x, w, b), tokens, rows), args))(x, w, b)

        one, d_one = grads(4)
        some, d_some = grads(rows)
        np.testing.assert_allclose(some, one, rtol=1e-6)
        for a, c in zip(d_some, d_one):
            assert a.dtype == c.dtype
            np.testing.assert_allclose(a.astype(jnp.float32), c.astype(jnp.float32), atol=3e-7)
        with pytest.raises(ValueError, match="do not divide"):
            head_loss(HeadInputs(x, w, b), tokens, 3)

    def test_chunk_rows_come_from_the_shapes(self):
        from katib_tpu.models.lm_head import CHUNK_BYTES, chunk_rows

        assert chunk_rows(8, 1024, 50257) == 4  # gpt2-small: two chunks of 0.82 GB
        assert chunk_rows(2, 4096, 16032) == 2  # the whole array is 0.53 GB: one chunk
        assert chunk_rows(4, 16, 32) == 4  # every CPU test: one chunk
        assert chunk_rows(6, 1024, 100_000) == 2  # a divisor of the batch: not 4, which fits too
        assert chunk_rows(3, CHUNK_BYTES, 2) == 1  # one sequence passes the limit: still one a chunk

    @pytest.mark.parametrize("form", ["dense", "head"])
    def test_gradient_program_holds_no_log_softmax_and_no_scatter(self, form):
        from katib_tpu.models.lm_head import HeadInputs
        from katib_tpu.models.transformer import lm_loss

        x, w, b, tokens = _head_case(jnp.bfloat16)
        big = (*tokens.shape, w.shape[1])
        if form == "dense":
            logits = jnp.dot(x.astype(jnp.float32), w) + b
            ours = jax.make_jaxpr(jax.grad(lm_loss))(logits, tokens)
            plain = jax.make_jaxpr(jax.grad(_plain_lm_loss))(logits, tokens)
            most = 7  # forward: l - max, exp; backward: l - lse, exp, the hit as float32, p - hit, the scale
        else:
            ours = jax.make_jaxpr(jax.grad(lambda x, w, b: lm_loss(HeadInputs(x, w, b), tokens), (0, 1, 2)))(x, w, b)
            plain = jax.make_jaxpr(jax.grad(_plain_head_loss, (0, 1, 2)))(x, w, b, tokens)
            most = 9  # and the product, and its bias
        n, names = _results_of_shape(ours.jaxpr, big)
        sliced = (big[0], big[1] - 1, big[2])  # the plain formula slices to S - 1 and pads back
        n_plain, names_plain = _results_of_shape(plain.jaxpr, big)
        n_plain += _results_of_shape(plain.jaxpr, sliced)[0]
        # the test's own eyes: the plain formula shows what must not be here
        assert "log_softmax" in names_plain and any(p.startswith("scatter") for p in names_plain)
        assert "log_softmax" not in names and not any(p.startswith("scatter") for p in names)
        assert n <= most < n_plain, (n, n_plain)



def _old_way_losses(model, data, *, lr, steps, batch_size, warmup_frac, grad_clip=1.0, seed=0):
    """The loop ``train_lm`` had before its programs were shared: the schedule
    a constant of ``optax.adamw``, closures of this one call, eager init."""
    import optax

    from katib_tpu.parallel.train import TrainState, clip_by_global_norm

    lm_loss = _plain_lm_loss  # the formula written out: independent of the program's
    rng = np.random.default_rng(seed)
    n_eval = max(batch_size, len(data) // 10)
    train, heldout = data[:-n_eval], data[-n_eval:]
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, data.shape[1]), jnp.int32))
    sched = optax.warmup_cosine_decay_schedule(0.0, lr, max(1, int(steps * warmup_frac)), steps)
    tx = optax.adamw(sched, weight_decay=0.01)

    @jax.jit
    def step_fn(state, tokens):
        loss, grads = jax.value_and_grad(lambda p: lm_loss(model.apply(p, tokens), tokens))(state.params)
        grads, _ = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        return TrainState(state.step + 1, optax.apply_updates(state.params, updates), opt_state), loss

    @jax.jit
    def eval_fn(params, tokens):
        return lm_loss(model.apply(params, tokens), tokens)

    state = TrainState.create(params, tx)
    eval_tokens = jnp.asarray(heldout[:batch_size])
    out = []
    for _ in range(steps):
        tokens = jnp.asarray(train[rng.integers(0, len(train), size=batch_size)])
        state, loss = step_fn(state, tokens)
        out.append((float(loss), float(eval_fn(state.params, eval_tokens))))
    return out


class TestSameMathematics:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    @pytest.mark.parametrize("lr", [1e-3, 3e-2])
    @pytest.mark.parametrize("steps,warmup_frac", [(12, 0.1), (12, 0.5), (30, 0.25)])
    def test_losses_equal_the_constants_baked_in(self, lr, steps, warmup_frac, dtype):
        """Parameters, optimizer state and logits are float32 either way;
        ``dtype`` is the activations' (the program's default is bf16)."""
        from katib_tpu.models.transformer import train_lm

        model = _tiny_lm(dtype=dtype)
        got = []
        train_lm(
            model, _tiny_data(), lr=lr, steps=steps, batch_size=4, warmup_frac=warmup_frac,
            report=lambda step, loss, eval_loss: got.append((loss, eval_loss)) if step < 12 else None,
            report_every=1,
        )
        want = _old_way_losses(
            model, _tiny_data(), lr=lr, steps=steps, batch_size=4, warmup_frac=warmup_frac
        )[:12]
        assert len(got) == 12
        np.testing.assert_allclose(got, want, rtol=1e-5)
        assert want[11][0] < want[0][0]  # and it trained

    @pytest.mark.parametrize("steps,warmup_frac", [(40, 0.1), (12, 0.5), (7, 0.3), (1, 0.1)])
    def test_schedule_is_optax_warmup_cosine(self, steps, warmup_frac):
        import optax

        from katib_tpu.models.transformer import warmup_cosine

        warm = max(1, int(steps * warmup_frac))
        counts = jnp.arange(steps + 3, dtype=jnp.int32)
        got = jax.jit(jax.vmap(warmup_cosine, in_axes=(0, None, None, None)))(
            counts, jnp.float32(3e-3), jnp.int32(warm), jnp.int32(steps)
        )
        assert got[0] == 0.0 and np.all(np.isfinite(got))
        if steps > warm:  # optax refuses a schedule with no decay
            want = jax.vmap(optax.warmup_cosine_decay_schedule(0.0, 3e-3, warm, steps))(counts)
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-12)
            assert got[warm] == np.float32(3e-3) and abs(got[steps]) < 1e-9

    def test_dropout_runs_and_is_finite(self):
        from katib_tpu.models.transformer import train_lm

        got = []
        final = train_lm(
            _tiny_lm(dropout=0.1), _tiny_data(), lr=1e-3, steps=12, batch_size=4,
            report=lambda step, loss, eval_loss: got.append((loss, eval_loss)),
        )
        assert len(got) == 3 and np.all(np.isfinite(got)) and np.isfinite(final)
