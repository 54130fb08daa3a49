"""Compile the main path's device programs for a TPU v5e that is described,
not attached: the chip's own compiler refuses here what it would refuse
there (misaligned kernel tiles, too much fast memory, a program that does
not fit 16 GiB), at no chip time.

Everything that touches the TPU library happens inside the module-scoped
``topo`` fixture or a test body — never at import, never in a child
process: only one process may load libtpu, and under pytest-xdist every
worker imports this file (guide: on-chip-measurement, section 2).  The
persistent compile cache is off around these compiles; a deviceless
executable written to it could not be read back without a chip.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (n_ops, batch, h, w, c): the normal cells of the flagship supernet
# (8 primitives, batch 64, 16 channels doubling at each reduction)
MIXED_OP_SHAPES = [(8, 64, 32, 32, 16), (8, 64, 16, 16, 32), (8, 64, 8, 8, 64)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", MIXED_OP_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_mixed_op_kernel_compiles(one_chip, shape, dtype):
    from katib_tpu.ops.mixed_op import _pallas_mixed_op

    w = jax.ShapeDtypeStruct(shape[:1], jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(lambda w, x: _pallas_mixed_op(w, x, False)).lower(w, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _attention_kernels(one_chip, q_shape, v_shape, grad, window=None):
    """The ``tpu_custom_call`` lines of the attention program (forward, or
    forward and backward under ``jax.grad``) at the tiles the kernel plans,
    compiled for the described chip.  Keys have the values' heads and the
    queries' width."""
    from katib_tpu.ops.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False, window=window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct(v_shape[:3] + q_shape[3:], jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16, sharding=one_chip)
    text = jax.jit(fn).lower(q, k, v).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    # the benchmark's marks find a kernel by its first operand: q, bfloat16
    # (families/mla_moe.py FLASH_KERNEL_MARK); int32 first is how it finds the
    # grouped products (EXPERT_PRODUCT_MARK)
    for line in kernels:
        assert "operand_layout_constraints={bf16[" in line and "{s32[" not in line, line
    return kernels


# the shapes that run the kernel on the chip: the benchmark's cells
# ([batch, heads, positions, width] of q, then of v, then the window) and a
# longer context
ATTENTION_SHAPES = {
    "gpt2-small": ((8, 12, 1024, 64), (8, 12, 1024, 64), None),
    "kanana-2-30b-a3b-ep8": ((2, 32, 4096, 192), (2, 32, 4096, 128), None),
    "long-context": ((4, 8, 4096, 64), (4, 8, 4096, 64), None),
    # 28 query heads over 4 key-value heads at 16384 positions: the layer that
    # sees the whole prefix, and the three that see 4096 keys
    "smallthinker-21b-a3b-ep8-full": ((1, 28, 16384, 128), (1, 4, 16384, 128), None),
    "smallthinker-21b-a3b-ep8-window": ((1, 28, 16384, 128), (1, 4, 16384, 128), 4096),
    # 16 heads of 128 over as many key-value heads: every application of a layer
    "ouro-2.6b-l6": ((1, 16, 4096, 128), (1, 16, 4096, 128), None),
    # twice the third cell's keys: the walk's accumulators pass the kernel's
    # VMEM limit, so dq and dkv run
    "beyond-the-walk": ((1, 4, 32768, 128), (1, 2, 32768, 128), None),
}


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("name", sorted(ATTENTION_SHAPES))
def test_flash_attention_compiles(one_chip, name, grad):
    """A whole K and V of one head and, in the backward's one walk, its dk
    and dv and two float32 accumulators as long sit in VMEM beside the tiles:
    at 16384 positions of width 128 too.  Two kernels a layer where the walk
    is planned (all four cells), forward, dq and dkv where it is not."""
    from katib_tpu.ops.flash_attention import one_walk, plan_tiles

    q_shape, v_shape, window = ATTENTION_SHAPES[name]
    kernels = _attention_kernels(one_chip, q_shape, v_shape, grad, window)
    shape = (q_shape[2], v_shape[2], q_shape[3], v_shape[3], jnp.bfloat16)
    walk = one_walk(*shape, *plan_tiles(*shape))
    assert walk == (name != "beyond-the-walk")
    assert len(kernels) == ((2 if walk else 3) if grad else 1)


# the largest lengths (a multiple of the larger tile) at which ``one_walk``
# still says yes, by dtype, widths and tiles: (keys, key width, value width,
# dtype, (q tile, k tile)) and the estimate in MiB, just under the 64 of
# ``VMEM_LIMIT_BYTES``
WALK_EDGES = [
    (19968, 128, 128, jnp.bfloat16, (512, 512), 63.6),
    (15360, 128, 128, jnp.bfloat16, (1024, 1024), 63.1),
    (12288, 192, 128, jnp.bfloat16, (512, 1024), 63.7),
    (11776, 128, 128, jnp.float32, (512, 512), 63.3),
    (8192, 128, 128, jnp.float32, (1024, 1024), 59.6),
    (4096, 256, 256, jnp.float32, (1024, 1024), 63.1),
]


@pytest.mark.parametrize(
    "seq,d_k,d_v,dtype,tiles,mib", WALK_EDGES,
    ids=[f"{jnp.dtype(e[3]).name}-{e[0]}x{e[1]}-q{e[4][0]}k{e[4][1]}" for e in WALK_EDGES],
)
def test_the_walk_compiles_up_to_its_rule(one_chip, seq, d_k, d_v, dtype, tiles, mib):
    """Wherever ``one_walk`` sends a call to the single walk the chip's
    compiler takes it under the kernel's own ``vmem_limit_bytes``: float32
    operands and 1024-wide score tiles at the edge of the rule too (one more
    tile of keys and the rule says dq + dkv)."""
    from katib_tpu.ops.flash_attention import flash_attention, one_walk, vmem_bytes

    assert vmem_bytes(seq, seq, d_k, d_v, dtype, *tiles, "walk") / 2**20 == pytest.approx(mib, abs=0.05)
    assert one_walk(seq, seq, d_k, d_v, dtype, *tiles)
    assert not one_walk(seq + max(tiles), seq + max(tiles), d_k, d_v, dtype, *tiles)

    def loss(q, k, v):
        out = flash_attention(q, k, v, interpret=False, block_q=tiles[0], block_k=tiles[1])
        return jnp.sum(out.astype(jnp.float32))

    q, k, v = (
        jax.ShapeDtypeStruct((1, heads, seq, width), dtype, sharding=one_chip)
        for heads, width in ((2, d_k), (1, d_k), (1, d_v))
    )
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2  # forward, the walk


def test_grouped_expert_product_is_a_kernel(one_chip):
    """``jax.lax.ragged_dot`` and both products of its transpose compile to
    grouped-product kernels on the TPU, not to a dense product over all
    experts with a mask (models/mla_moe.py relies on it; the benchmark's
    EXPERT_PRODUCT_MARK finds them by their int32 group metadata)."""

    def loss(x, w, sizes):
        return jnp.sum(jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32))

    x = jax.ShapeDtypeStruct((49152, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((16, 2048, 768), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w, sizes).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) >= 2 and all("operand_layout_constraints={s32[" in k for k in kernels)
    assert "bf16[16,49152" not in text  # no [experts, rows, ...] dense intermediate


def test_looped_step_fits_one_chip_and_holds_the_stack_once(one_chip):
    """The whole train step of ``ouro-2.6b-l6`` (``block: looped``: six layers
    run four times, 510M parameters, 4096 positions) compiled for the
    described chip, the attention kernel in it.  Its ``memory_analysis()`` is
    what decides the configuration's depth (ISSUE 37: over 14 GB, cut a layer;
    under 8 GB, say so): it read 11.25 GB at six layers, and 11.66 GB (6.12
    held in place, 5.36 temporaries, 0.19 code) since the rematerialised
    blocks keep the attention kernel's output and logsumexp of all 24 layer
    applications (PR 38).

    The rule is held on ``peak_memory_in_bytes``, the most the compiler's
    schedule has live at once, arguments included: 11.54 GB with the
    backward's one walk (PR 40), 10.74 with dq + dkv, and 10.74 for both when
    the chip compiles the step itself (its allocator then reserves 4.66 GB
    beside 6.32 in use: PERF.md section 6, PR 40).  ``temp_size_in_bytes`` is
    how that schedule's temporaries happened to pack, and this deviceless
    compile lands in one of two packings about 3 GB apart from one depth to
    the next (dq + dkv: 7.35 GB at five layers, 5.36 at six; the walk: 4.68
    at two passes, 8.38 at four; the chip's own compile counts 5.51 for
    either), so the sum with it is held only to the chip's 16 GiB.  The
    passes are a loop, and the backward loop's body holds no forward kernel:
    12 attention kernels (the forward and the backward's one walk of six
    layers), not 48."""
    import json

    from katib_tpu.models import transformer
    from katib_tpu.models.looped import LoopedLM, LoopedSizes
    from katib_tpu.ops.flash_attention import flash_attention

    def kernel(q, k, v):  # what make_attention_fn gives on the chip
        return flash_attention(q, k, v, causal=True, interpret=False)

    kernel.kernel, kernel.window = True, None
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmark", "configs", "ouro-2.6b-l6.json")) as f:
        cfg = json.load(f)
    sizes = LoopedSizes(
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        mlp_width=cfg["intermediate_size"], n_layers=cfg["num_hidden_layers"], ut_steps=cfg["total_ut_steps"],
    )
    model = LoopedLM(vocab_size=cfg["vocab_size"], sizes=sizes, attn_fn=kernel)
    programs = transformer._build_programs(model, 1.0, transformer.WEIGHT_DECAY, None)
    seq_len = cfg["seq_len"]

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(placed, jax.eval_shape(programs.init, jax.random.PRNGKey(0), seq_len))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)  # noqa: E731
    compiled = programs.step_fn.lower(
        state, placed(jnp.zeros((cfg["batch_size"], seq_len), jnp.int32)), placed(jnp.zeros((2,), jnp.uint32)),
        scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.int32),
    ).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2 * sizes.n_layers
    ma = compiled.memory_analysis()
    held = ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
    total = held + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
    assert 6.0e9 < held < 6.3e9  # parameters and two moments, float32, updated in place
    assert 8e9 < ma.peak_memory_in_bytes + ma.generated_code_size_in_bytes < 14e9, ma  # the rule's two ends
    assert total < 16 * 2**30, total  # however the temporaries pack


def _mnist_cohort_step_avals(k, member_sharding, shared_sharding, mesh=None):
    """The jitted cohort train step of ``mnist_cohort_trial`` (MLP, units
    64, batch 256) and its operands as shapes placed on the described
    devices: stacked ``[K, ...]`` member states, one shared batch."""
    from katib_tpu.models import mnist
    from katib_tpu.parallel.train import TrainState, stack_pytrees

    model = mnist.MLP(units=64, num_layers=2)
    tx, step, _evaluate = mnist._build_cohort_steps(model, "momentum", mesh)

    def stacked_state():
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1), jnp.float32))
        return stack_pytrees([TrainState.create(params, tx)] * k)

    def place(sharding):
        return lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    states = jax.tree.map(place(member_sharding), jax.eval_shape(stacked_state))
    batch = (
        jax.ShapeDtypeStruct((256, 28, 28, 1), jnp.float32, sharding=shared_sharding),
        jax.ShapeDtypeStruct((256,), jnp.int32, sharding=shared_sharding),
    )
    return step, states, batch


def _fits_v5e(compiled) -> bool:
    """Arguments + outputs + temporaries + code (``memory_analysis()``, as
    ``cost_of_compiled`` sums them) against the table's 16 GiB."""
    from katib_tpu.costmodel.peaks import PEAKS
    from katib_tpu.costmodel.record import cost_of_compiled

    return 0 < cost_of_compiled(compiled).hbm_bytes < PEAKS["v5e"].hbm_bytes


def test_mnist_cohort_step_one_chip(one_chip):
    step, states, batch = _mnist_cohort_step_avals(4, one_chip, one_chip)
    compiled = step.lower(states, batch).compile()
    assert _fits_v5e(compiled)


def test_mnist_cohort_step_trial_sharded_four_chips(topo):
    """K=8 with the member dimension split over a {trial: 4} mesh of the
    described devices: each device steps K/4 members of one SPMD program."""
    from katib_tpu.parallel.mesh import TRIAL_AXIS

    mesh = Mesh(np.asarray(topo.devices[:4]), (TRIAL_AXIS,))
    members = NamedSharding(mesh, PartitionSpec(TRIAL_AXIS))
    shared = NamedSharding(mesh, PartitionSpec())
    step, states, batch = _mnist_cohort_step_avals(8, members, shared, mesh)
    compiled = step.lower(states, batch).compile()
    assert _fits_v5e(compiled)
    state_out, _metrics = compiled.output_shardings
    for sharding in jax.tree.leaves(state_out):
        assert sharding.spec[0] == TRIAL_AXIS, sharding
