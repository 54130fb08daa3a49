"""A rematerialised block keeps the attention kernel's output and logsumexp
(``ops/flash_attention.py``: ``KERNEL_RESULTS``, ``remat_block``): the forward
kernel runs once a layer and step, the gradient is the unrematerialised one,
and ``trial.init`` says what runs (``attention_plan``'s ``remat``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from katib_tpu.models import transformer
from katib_tpu.models.gqa_moe import GqaMoeBlock, GqaMoeLM, GqaMoeSizes
from katib_tpu.models.looped import LoopedBlock, LoopedLM, LoopedSizes
from katib_tpu.models.mla_moe import MlaMoeBlock, MlaMoeLM, MlaMoeSizes
from katib_tpu.ops.flash_attention import (
    KERNEL_RESULTS,
    flash_attention,
    flash_attention_with_lse,
    remat_block,
)
from katib_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, make_mesh
from katib_tpu.parallel.ring_attention import make_sequence_parallel_attention


REMAT = "remat2["  # ``jax.checkpoint`` as a gradient's program spells it


def _kernel(window=None, interpret=False):
    """What ``make_attention_fn`` gives on the chip (``interpret``: the same
    kernels run by the interpreter, for values on the CPU)."""

    def attention(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret, window=window)

    attention.kernel, attention.window = True, window
    return attention


def _dense(window=None):
    return transformer._single_device_attention(False, window)


MLA = MlaMoeSizes(d_model=64, n_heads=2, n_layers=2, n_experts=4, experts_held=(0, 4))
GQA = GqaMoeSizes(d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, n_layers=4, window=128, n_experts=4, experts_held=(0, 4))
LOOPED = LoopedSizes(d_model=64, n_heads=2, head_dim=32, mlp_width=128, n_layers=3, ut_steps=4)


def _models(attention):
    """A tiny model of every block family; ``attention(window)`` is its attention."""
    return {
        "gpt2": transformer.TransformerLM(vocab_size=64, d_model=64, n_heads=2, n_layers=2, max_seq_len=256, attn_fn=attention()),
        "mla_moe": MlaMoeLM(vocab_size=64, sizes=MLA, attn_fn=attention()),
        "gqa_moe": GqaMoeLM(vocab_size=64, sizes=GQA, attn_fn=attention(), window_attn_fn=attention(GQA.window)),
        "looped": LoopedLM(vocab_size=64, sizes=LOOPED, attn_fn=attention()),
    }


def _layers(model) -> int:
    return sum(n for _window, _positions, n in model.attn_kinds)


def _traced_step(model, seq_len=256):
    programs = transformer._build_programs(model, 1.0, transformer.WEIGHT_DECAY, None)
    state = jax.eval_shape(programs.init, jax.random.PRNGKey(0), seq_len)
    return programs.step_fn.trace(
        state, jax.ShapeDtypeStruct((1, seq_len), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.uint32),
        jnp.float32(1e-3), jnp.int32(1), jnp.int32(12),
    )


class TestTheStepHoldsTheForwardKernelOnce:
    @pytest.mark.parametrize("family", ["mla_moe", "gqa_moe", "looped"])
    def test_two_attention_kernels_a_layer_under_remat(self, family):
        """The step lowered for a TPU (no chip and no compile: the text of
        what the compiler would be handed): the forward and the backward's one
        walk of every layer (the looped model's passes are a loop), no second
        forward."""
        model = _models(_kernel)[family]
        traced = _traced_step(model)
        assert REMAT in str(traced.jaxpr)  # the blocks are rematerialised
        assert traced.lower(lowering_platforms=("tpu",)).as_text().count("tpu_custom_call") == 2 * _layers(model)

    def test_gpt2_keeps_everything_and_is_not_rematerialised(self):
        model = _models(_kernel)["gpt2"]
        traced = _traced_step(model)
        jaxpr, text = str(traced.jaxpr), traced.lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 2 * _layers(model)
        assert REMAT not in jaxpr and "optimization_barrier" not in text
        # the names are in the program and lower to nothing
        assert all(name in jaxpr for name in KERNEL_RESULTS) and not any(name in text for name in KERNEL_RESULTS)

    @pytest.mark.parametrize("family", ["mla_moe", "gqa_moe", "looped"])
    def test_dense_attention_carries_no_name(self, family):
        """No kernel, nothing named: the policy keeps nothing, as a bare
        ``nn.remat`` does."""
        jaxpr = str(_traced_step(_models(_dense)[family], seq_len=32).jaxpr)
        assert REMAT in jaxpr and not any(name in jaxpr for name in KERNEL_RESULTS)


# ---------------------------------------------------------------------------
# the gradient under the helper is the unrematerialised gradient
# ---------------------------------------------------------------------------

BLOCKS = {
    # 48-wide keys (32 + 16 rotary) over 32-wide values, the leading dense layer
    "mla_moe-v32-dense": lambda attn: (MlaMoeBlock, (MLA, True, attn(), jnp.float32)),
    # values wider than the keys, an expert layer
    "mla_moe-v64-experts": lambda attn: (
        MlaMoeBlock, (MlaMoeSizes(**{**vars(MLA), "v_head_dim": 64}), False, attn(), jnp.float32),
    ),
    # 4 query heads over 2 key-value heads: the whole prefix without positions, a window with rotary
    "gqa_moe-full": lambda attn: (GqaMoeBlock, (GQA, False, attn(), jnp.float32)),
    "gqa_moe-window": lambda attn: (GqaMoeBlock, (GQA, True, attn(GQA.window), jnp.float32)),
    "looped": lambda attn: (LoopedBlock, (LOOPED, attn(), jnp.float32)),
}


def _block_gradients(wrap, block, fields, x):
    """Parameters' and input's gradient of a scalar of the block's output,
    the block's class wrapped by ``wrap``; and the gradient's program."""
    params = block(*fields).init(jax.random.PRNGKey(1), x)

    def loss(params, x):
        return jnp.sum(jnp.sin(wrap(block)(*fields).apply(params, x)))

    traced = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x)
    return traced.lower().compile()(params, x), str(traced.jaxpr)


@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_block_under_the_helper_gives_the_plain_blocks_gradient(case):
    block, fields = BLOCKS[case](lambda window=None: _kernel(window, interpret=True))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64), jnp.float32)
    plain, plain_program = _block_gradients(lambda b: b, block, fields, x)
    kept, kept_program = _block_gradients(remat_block, block, fields, x)
    bare, bare_program = _block_gradients(nn.remat, block, fields, x)
    for want, got, again in zip(jax.tree.leaves(plain), jax.tree.leaves(kept), jax.tree.leaves(bare)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(again, want, rtol=1e-6, atol=1e-6)
    # forward and the backward's one walk; a bare nn.remat runs the forward kernel again
    assert plain_program.count("pallas_call") == kept_program.count("pallas_call") == 2
    assert bare_program.count("pallas_call") == 3
    assert REMAT in kept_program and REMAT not in plain_program


class _RingBlock(nn.Module):
    """Projections around sequence-parallel attention: a block whose attention
    runs the kernel inside a scan inside a ``shard_map``."""

    attn_fn: object

    @nn.compact
    def __call__(self, x):  # [B, S, D], two heads
        b, s, d = x.shape
        q, k, v = (
            nn.Dense(d, use_bias=False, name=name)(x).reshape(b, s, 2, d // 2).transpose(0, 2, 1, 3)
            for name in ("q", "k", "v")
        )
        o = self.attn_fn(q, k, v).transpose(0, 2, 1, 3).reshape(b, s, d)
        return x + nn.Dense(d, use_bias=False, name="o")(o)


def test_ring_attention_under_the_helper_gives_the_plain_gradient():
    """Each device's chunks go through the kernel (interpreted), whose results
    carry the names inside the ring's scan and branches."""
    mesh = make_mesh({DATA_AXIS: 2, SEQ_AXIS: 4})

    def inner(q, k, v, causal):  # positional: custom_vjp functions reject keyword arguments
        return flash_attention_with_lse(q, k, v, causal, None, None, None, True)

    attn = make_sequence_parallel_attention(mesh, strategy="ring", causal=True, inner=inner)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 512, 16), jnp.float32)
    plain, plain_program = _block_gradients(lambda b: b, _RingBlock, (attn,), x)
    kept, kept_program = _block_gradients(remat_block, _RingBlock, (attn,), x)
    assert all(name in plain_program for name in KERNEL_RESULTS)
    assert REMAT in kept_program and REMAT not in plain_program
    # forward and the one walk back in the branch of an earlier chunk and in the diagonal's:
    # the policy reaches the names through the scan and the switch
    assert plain_program.count("pallas_call") == kept_program.count("pallas_call") == 4
    for want, got in zip(jax.tree.leaves(plain), jax.tree.leaves(kept)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# trial.init says what runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "dense"])
@pytest.mark.parametrize("family", ["gpt2", "mla_moe", "gqa_moe", "looped"])
def test_attention_plan_says_what_the_backward_pass_computes_again(family, kernel):
    model = _models(lambda window=None: transformer._single_device_attention(kernel, window))[family]
    attrs, counters = transformer.attention_plan(model, 1, 1024)
    want = "none" if family == "gpt2" else "blocks, keeps attn out+lse" if kernel else "blocks"
    assert attrs["remat"] == want and model.BLOCK == family
    # every tile the step walks is counted: forward and the backward's one walk, once each
    assert bool(counters) == kernel
    if kernel:
        assert attrs["attn_tiles"].endswith(", backward one walk")
        assert counters["attn_tiles_run"] % 2 == 0 and counters["attn_tiles_run"] >= counters["attn_tiles_needed"]


def test_trial_init_carries_remat(tmp_path):
    from katib_tpu.utils import tracing

    tracer = tracing.Tracer(str(tmp_path / "trace.jsonl"))
    data = transformer.markov_dataset(64, 12, 32, seed=1)
    with tracing.use_tracer(tracer), tracing.span("train_fn", trial="t0"):
        transformer.train_lm(_models(_dense)["looped"].clone(dtype=jnp.float32), data, lr=1e-3, steps=1, batch_size=1)
    tracer.close()
    init = [r["args"] for r in tracing.read_journal(tracer.path) if r["name"] == "trial.init"]
    assert [a["remat"] for a in init] == ["blocks"]
