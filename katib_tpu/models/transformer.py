"""Tunable long-context transformer LM — the sequence-parallel trial workload.

The reference's trial zoo stops at small CNNs (SURVEY.md §2.3); it has no
long-context model family because it has no sequence parallelism.  This
module adds a decoder-only transformer whose attention runs through the
fused flash kernel (``katib_tpu.ops.flash_attention``) on one chip and
through ring / all-to-all sequence parallelism
(``katib_tpu.parallel.ring_attention``) when the trial's mesh has a ``seq``
axis — so HP search (lr, width, depth, heads) can drive long-sequence
training on a sharded mesh with the same trial API as the CNN workloads.

Tunable parameters understood by ``transformer_trial``: lr, d_model,
n_heads, n_layers, seq_len, vocab_size, batch_size, n_seq, data_seed, steps,
warmup_frac, attn(ring|ulysses), dropout, and
block(gpt2|mla_moe|gqa_moe|looped).
With ``block: mla_moe`` (latent attention and sparse experts,
``katib_tpu.models.mla_moe``) also: first_dense_layers, qk_nope_dim,
qk_rope_dim, v_head_dim, kv_lora_rank, dense_width, expert_width, n_experts,
experts_per_token, n_shared_experts, routed_scaling, rope_theta, eps, and the
share of the routed experts this trial holds: experts_held_first,
experts_held (default: all).  With ``block: gqa_moe`` (grouped-query
attention in a period of layer kinds, experts routed from the layer's input,
``katib_tpu.models.gqa_moe``) also: n_kv_heads, head_dim, window,
window_layout and rope_layout (a period of layer kinds as a string of 0 and
1, one character a kind: ``"0111"``; 1 sees ``window`` keys / carries rotary
positions), expert_width, n_experts, experts_per_token, rope_theta, eps,
experts_held_first, experts_held.  With ``block: looped`` (a stack of layers
run ``ut_steps`` times over the same weights, an exit after every pass, a
learned exit gate, ``katib_tpu.models.looped``) also: head_dim, mlp_width,
ut_steps, exit_beta, rope_theta, eps.  Every block but ``gpt2`` refuses
dropout and a ``seq`` mesh axis.

**The loss belongs to the model.**  A model answers ``training_loss(outputs,
tokens)`` with the loss a step differentiates and what a report may read of
it (a tree; ``step_counters`` turns it into a span's attributes), and
``reported_loss(outputs, tokens)`` with the ``eval_loss`` a trial reports;
``outputs`` is what its ``apply`` returns.  For the first three blocks both
are the next-token cross entropy (``lm_loss``); ``block: looped`` trains on
its expected-exit objective and reports its last exit's cross entropy.

The training task is a synthetic first-order Markov language-modelling
problem: next-token structure is learnable (entropy well below uniform) and
the data is generated on the fly, so trials are hermetic — no dataset
download, the objective (validation loss) still orders hyperparameters
meaningfully.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from contextlib import nullcontext
from functools import lru_cache, partial
from typing import Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from katib_tpu.models.gqa_moe import GqaMoeLM, GqaMoeSizes
from katib_tpu.models.lm_head import LMHead, chunk_rows, lm_loss
from katib_tpu.models.looped import LoopedLM, LoopedSizes
from katib_tpu.models.mla_moe import ROUTING, MlaMoeLM, MlaMoeSizes, expert_buffer
from katib_tpu.ops.flash_attention import (
    flash_attention,
    one_walk,
    plan_tiles,
    reference_attention,
    tile_visits,
)
from katib_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, replicated, shard_batch
from katib_tpu.parallel.ring_attention import make_sequence_parallel_attention
from katib_tpu.parallel.train import TrainState, clip_by_global_norm
from katib_tpu.utils import tracing


class Block(nn.Module):
    d_model: int
    n_heads: int
    attn_fn: Callable  # (q, k, v) [B,H,S,D] -> [B,H,S,D]
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        d_head = self.d_model // self.n_heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * self.d_model, use_bias=False, dtype=self.dtype)(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # [B, S, D_model] -> [B, H, S, d_head]
            b, s, _ = t.shape
            return t.reshape(b, s, self.n_heads, d_head).transpose(0, 2, 1, 3)

        o = self.attn_fn(heads(q), heads(k), heads(v))
        b, nh, s, dh = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * dh).astype(self.dtype)
        o = nn.Dense(self.d_model, use_bias=False, dtype=self.dtype)(o)
        x = x + nn.Dropout(self.dropout, deterministic=deterministic)(o)

        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(4 * self.d_model, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(self.d_model, dtype=self.dtype)(h)
        return x + nn.Dropout(self.dropout, deterministic=deterministic)(h)


class TransformerLM(nn.Module):
    BLOCK = "gpt2"  # the block family's name, as ``transformer_trial`` takes it
    REMAT_BLOCKS = False  # the backward pass reads every block's kept activations

    vocab_size: int
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    max_seq_len: int = 2048
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    attn_fn: Callable | None = None  # default set in setup-free __call__

    @property
    def attn_widths(self) -> tuple[int, int]:
        """A head's key and value widths."""
        return (self.d_model // self.n_heads,) * 2

    @property
    def attn_heads(self) -> int:
        """Query heads: the kernels' grids walk one at a time."""
        return self.n_heads

    @property
    def attn_kinds(self) -> list[tuple[int | None, str, int]]:
        """(window, positions, layers) of each kind of attention layer."""
        return [(None, "learned", self.n_layers)]

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True, multiply_head: bool = True):
        attn = self.attn_fn
        if attn is None:
            attn = _dense_causal_attention
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype)(tokens)
        pos = nn.Embed(self.max_seq_len, self.d_model, dtype=self.dtype)(
            jnp.arange(tokens.shape[1])[None, :]
        )
        x = x + pos
        for _ in range(self.n_layers):
            x = Block(
                d_model=self.d_model, n_heads=self.n_heads, attn_fn=attn,
                dropout=self.dropout, dtype=self.dtype,
            )(x, deterministic)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return LMHead(self.vocab_size, name="Dense_0")(x, multiply_head)

    def training_loss(self, outputs, tokens):
        return lm_loss(outputs, tokens), {}

    def reported_loss(self, outputs, tokens):
        return lm_loss(outputs, tokens)


@lru_cache(maxsize=64)
def _single_device_attention(kernel: bool, window: int | None = None):
    """Causal attention on one device: the flash kernel or the dense
    reference, over the whole prefix or the ``window`` newest keys.  The same
    arguments give the same callable; it says what it runs (``kernel``,
    ``window``) to ``attention_plan``."""
    attend = flash_attention if kernel else reference_attention

    def attention(q, k, v):
        return attend(q, k, v, causal=True, window=window)

    attention.kernel, attention.window = kernel, window
    return attention


_dense_causal_attention = _single_device_attention(False)
_flash_causal_attention = _single_device_attention(True)


@lru_cache(maxsize=8)
def _sequence_parallel_attention(mesh, strategy: str):
    return make_sequence_parallel_attention(mesh, strategy=strategy, causal=True)


def make_attention_fn(mesh=None, strategy: str = "ring", window: int | None = None):
    """Attention for a trial's mesh: sequence-parallel when the mesh has a
    ``seq`` axis > 1, single-device flash/dense otherwise; ``window``: a layer
    that sees only so many keys (single-device only).  The same arguments
    give the same callable, so that models built from equal fields compare
    equal and share their programs (``_programs_for``)."""
    if mesh is None:
        return _single_device_attention(jax.default_backend() == "tpu", window)
    if window is not None:
        raise ValueError("make_attention_fn: no windowed attention over a mesh")
    return _sequence_parallel_attention(mesh, strategy)


def _planned_attention(model, seq_len: int) -> tuple[int, int, bool]:
    """The tiles the kernel plans for a trial's shapes, and whether its
    backward is the single walk there (``flash_attention.one_walk``)."""
    shape = (seq_len, seq_len, *model.attn_widths, jnp.dtype(model.dtype))
    bq, bk = plan_tiles(*shape)
    return bq, bk, one_walk(*shape, bq, bk)


def attn_tiles(model, seq_len: int) -> str:
    """What a trial's attention runs, for the ``trial.init`` span: the flash
    kernel's operand dtype, the tiles it plans for these shapes and the
    backward it takes there (``"bfloat16 q512 k512, backward one walk"``;
    ``"..., backward dq+dkv"`` where a head's accumulators do not fit);
    ``dense`` where no kernel runs, ``seq-parallel`` over a mesh's ``seq``
    axis."""
    if model.attn_fn is None or getattr(model.attn_fn, "kernel", None) is False:
        return "dense"
    if not hasattr(model.attn_fn, "kernel"):
        return "seq-parallel"
    bq, bk, walk = _planned_attention(model, seq_len)
    return f"{jnp.dtype(model.dtype).name} q{bq} k{bk}, backward {'one walk' if walk else 'dq+dkv'}"


def attention_plan(model, batch: int, seq_len: int) -> tuple[dict, dict]:
    """The attention of a trial as the ``trial.init`` span carries it.
    Attributes: ``attn_layers`` (the kinds of layer and how many of each:
    ``"full nope x1, window4096 rope x3"``; a model that runs its layers
    several times says so: ``"full rope x6, 4 passes"``, and ``passes``),
    ``attn_tiles`` and ``remat``: what the backward pass computes again
    (``"none"``: every activation is kept; ``"blocks"``: every block from its
    input; ``"blocks, keeps attn out+lse"``: but for the attention kernel's
    results, which ``remat_block`` keeps).  Counters, where the kernel runs:
    ``attn_tiles_run`` (the tiles the kernels' loops walk in one step: the
    forward and the backward's one walk, or forward, dq and dkv where those
    run; every application of a layer, head and batch row) and
    ``attn_tiles_needed`` (the least: the tiles of the planned size that hold
    a visible pair, once a walk)."""
    kinds = model.attn_kinds
    passes = getattr(model, "passes", 1)
    kernel = bool(getattr(model.attn_fn, "kernel", False))
    layers = ", ".join(
        f"{'full' if window is None else f'window{window}'} {positions} x{n}"
        for window, positions, n in kinds
    )
    remat = "none"
    if model.REMAT_BLOCKS:
        remat = "blocks, keeps attn out+lse" if kernel else "blocks"
    attrs = {"attn_layers": layers, "attn_tiles": attn_tiles(model, seq_len), "remat": remat}
    if passes > 1:
        attrs.update(attn_layers=f"{layers}, {passes} passes", passes=passes)
    if not kernel:
        return attrs, {}
    bq, bk, walk = _planned_attention(model, seq_len)
    run = needed = 0
    for window, _positions, n in kinds:
        walked, least = tile_visits(seq_len, seq_len, bq, bk, True, window, walk=walk)
        run, needed = run + n * walked, needed + n * least
    rows = batch * model.attn_heads * passes
    return attrs, {"attn_tiles_run": rows * run, "attn_tiles_needed": rows * needed}


def loss_path(model, batch: int, seq_len: int, mesh) -> str:
    """How a trial's loss runs, for the ``trial.init`` span: ``fused`` on the
    dense logits a model on a mesh multiplies out, ``fused rows=<sequences a
    chunk> x <chunks>`` where the head's product runs inside it; a model with
    several exits sends every exit's sequences through the one chunk loop and
    says how many (``fused rows=1 x 4, 4 exits``)."""
    exits = getattr(model, "passes", 1)
    path = "fused"
    if mesh is None:
        rows = chunk_rows(batch * exits, seq_len, model.vocab_size)
        path = f"fused rows={rows} x {batch * exits // rows}"
    return path if exits == 1 else f"{path}, {exits} exits"


# ---------------------------------------------------------------------------
# synthetic Markov LM data
# ---------------------------------------------------------------------------


def markov_dataset(
    vocab_size: int, n_seq: int, seq_len: int, *, seed: int = 0, branching: int = 4
) -> np.ndarray:
    """Token sequences from a fixed sparse first-order Markov chain: every
    token has ``branching`` likely successors, so the optimal next-token loss
    is ≈ log(branching) — far below log(vocab) for an untrained model."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab_size, size=(vocab_size, branching))
    out = np.empty((n_seq, seq_len), np.int32)
    state = rng.integers(0, vocab_size, size=n_seq)
    for t in range(seq_len):
        out[:, t] = state
        pick = rng.integers(0, branching, size=n_seq)
        state = succ[state, pick]
    return out


# ---------------------------------------------------------------------------
# training loop
# ---------------------------------------------------------------------------


#: AdamW's decoupled weight decay, on every parameter
WEIGHT_DECAY = 0.01


def warmup_cosine(count, peak, warmup_steps, steps):
    """``optax.warmup_cosine_decay_schedule(0.0, peak, warmup_steps, steps)``
    at ``count``, in its float32 arithmetic, with every argument an operand of
    the program: linear from 0 to ``peak`` over ``warmup_steps`` updates, then
    a cosine to 0 at ``steps``."""
    warm = peak - peak * (1 - jnp.clip(count, 0, warmup_steps) / warmup_steps)
    span = jnp.maximum(steps - warmup_steps, 1).astype(jnp.float32)
    t = jnp.minimum(count - warmup_steps, span)
    cosine = peak * (0.5 * (1 + jnp.cos(jnp.pi * t / span)))
    return jnp.where(count < warmup_steps, warm, cosine)


class TrialPrograms(NamedTuple):
    """The jitted programs of one trial structure; each compiles on its first
    call and for each new shape, as any jitted function does."""

    init: Callable  # (key, seq_len) -> TrainState
    # (state, tokens, dropout_key, lr, warmup_steps, steps) -> (state, loss,
    # counters): the model's training loss, and what a report may read of the
    # step: what the expert layers sowed into ``ROUTING`` and what the loss
    # gave beside its value; an empty tree for a model that has neither
    step_fn: Callable
    eval_fn: Callable  # (params, tokens) -> the model's reported loss


def _build_programs(
    model: TransformerLM | MlaMoeLM | GqaMoeLM | LoopedLM, grad_clip: float, weight_decay: float, mesh
) -> TrialPrograms:
    # AdamW without its rate: ``step_fn`` scales the update by the schedule's
    # value, so lr, steps and warmup_frac are operands and not constants
    tx = optax.chain(optax.scale_by_adam(), optax.add_decayed_weights(weight_decay))
    use_dropout = getattr(model, "dropout", 0.0) > 0.0
    # init batch must divide the mesh's data axis (the attention shard_map
    # shards the batch dimension even while tracing init)
    init_batch = 1
    if mesh is not None and DATA_AXIS in mesh.shape:
        init_batch = mesh.shape[DATA_AXIS]

    # on one device the head's product runs inside the loss, in row chunks;
    # over a mesh the model multiplies it out (the chunk loop would run over
    # the axis a ``data`` mesh shards) and the loss takes dense logits
    multiply_head = mesh is not None

    def loss_fn(params, tokens, dropout_key):
        dropout = {"deterministic": False, "rngs": {"dropout": dropout_key}} if use_dropout else {}
        outputs, sown = model.apply(params, tokens, mutable=[ROUTING], multiply_head=multiply_head, **dropout)
        loss, read = model.training_loss(outputs, tokens)
        return loss, {**sown.get(ROUTING, {}), **read}

    # parameters and optimizer state in one program (the forward pass that
    # ``model.init`` traces is dead code in it), replicated over the mesh
    @partial(
        jax.jit, static_argnums=1, out_shardings=None if mesh is None else replicated(mesh)
    )
    def init(key, seq_len):
        params = model.init(key, jnp.zeros((init_batch, seq_len), jnp.int32))
        return TrainState.create(params, tx)

    # donate the state: params + optimizer buffers are dead after the step,
    # so XLA updates them in place instead of copying each iteration
    @partial(jax.jit, donate_argnums=(0,))
    def step_fn(state: TrainState, tokens, dropout_key, lr, warmup_steps, steps):
        (loss, routing), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, tokens, dropout_key
        )
        grads, _ = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        rate = warmup_cosine(state.step, lr, warmup_steps, steps)
        updates = jax.tree_util.tree_map(lambda u: -rate * u, updates)
        params = optax.apply_updates(state.params, updates)
        return TrainState(state.step + 1, params, opt_state), loss, routing

    @jax.jit
    def eval_fn(params, tokens):
        return model.reported_loss(model.apply(params, tokens, multiply_head=multiply_head), tokens)

    return TrialPrograms(init, step_fn, eval_fn)


# (model, grad_clip, weight decay, mesh) -> TrialPrograms: every trial of a
# process with the same structure calls the same jitted functions, so they are
# traced, lowered and loaded once.  flax Modules hash by field values.
# LRU-bounded: a search over d_model or n_layers must not pin executables for
# the life of the process.
_PROGRAMS: OrderedDict = OrderedDict()
_PROGRAMS_MAX = 8
_PROGRAMS_LOCK = threading.Lock()


def _programs_for(
    model: TransformerLM | MlaMoeLM | GqaMoeLM | LoopedLM, grad_clip: float, mesh
) -> tuple[TrialPrograms, bool]:
    """The structure's programs, and whether the process had them already."""
    key = (model, float(grad_clip), WEIGHT_DECAY, mesh)
    try:
        hash(key)
    except TypeError:  # an unhashable attn_fn: nothing to share
        return _build_programs(*key), False
    # building only wraps closures in jax.jit (tracing waits for the first
    # call), so it fits under the lock and no thread sees a half-built entry
    with _PROGRAMS_LOCK:
        programs = _PROGRAMS.get(key)
        reused = programs is not None
        if not reused:
            programs = _PROGRAMS[key] = _build_programs(*key)
        _PROGRAMS.move_to_end(key)
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    return programs, reused


def train_lm(
    model: TransformerLM | MlaMoeLM | GqaMoeLM | LoopedLM,
    data: np.ndarray,
    *,
    lr: float,
    steps: int,
    batch_size: int,
    warmup_frac: float = 0.1,
    grad_clip: float = 1.0,
    mesh=None,
    seed: int = 0,
    report=None,
    report_every: int = 10,
) -> float:
    """Train on ``data`` [N, S]; returns the final eval loss (the model's
    reported loss) on a held-out tail.  Calls ``report(step, loss, eval_loss)``
    every ``report_every`` steps; ``loss`` is the model's training loss."""
    # everything up to the loop: the structure's programs, parameters and
    # optimizer state, the schedule's operands, placing the eval tokens
    with tracing.span("trial.init") as sp:
        # host work alone, up to the first thing handed to the device
        # (``init``): where a trial's hand-over from the last one ends
        with tracing.span("trial.programs"):
            rng = np.random.default_rng(seed)
            n_eval = max(batch_size, len(data) // 10)
            train, heldout = data[:-n_eval], data[-n_eval:]

            programs, reused = _programs_for(model, grad_clip, mesh)
            attention, tile_counters = attention_plan(model, batch_size, data.shape[1])
            sp.set(
                programs="reused" if reused else "built",
                block=model.BLOCK,
                **attention,
                loss=loss_path(model, batch_size, data.shape[1], mesh),
            )
            for name, tiles in tile_counters.items():
                sp.add(name, tiles)
            if hasattr(getattr(model, "sizes", None), "experts_held"):
                # a model with expert layers: the lengths their sorted buffer may take
                sp.set(expert_buffer=expert_buffer(model.sizes, batch_size * data.shape[1]))
        state = programs.init(jax.random.PRNGKey(seed), data.shape[1])
        schedule = (
            jnp.float32(lr),
            jnp.int32(max(1, int(steps * warmup_frac))),
            jnp.int32(steps),
        )

        def place(tokens):
            tokens = jnp.asarray(tokens)
            return tokens if mesh is None else shard_batch(tokens, mesh)

        eval_tokens = place(heldout[:batch_size])
        eval_loss: float | None = None
        counters = None
        dkey = jax.random.PRNGKey(seed + 1)

    def evaluate(step: int, first: bool) -> float:
        with tracing.span("trial.eval", step=step, first=first) as sp:
            value = float(programs.eval_fn(state.params, eval_tokens))
            if counters:
                # what the last step gave a report to read (an expert model's
                # routing counts, a looped model's exits), as the model reads
                # it; fetched where the loss is: no wait of its own
                sp.set(**model.step_counters(jax.device_get(counters)))
            return value

    for s in range(steps):
        idx = rng.integers(0, len(train), size=batch_size)
        dkey, sub = jax.random.split(dkey)
        tokens = place(train[idx])
        # the first call alone, call to return (it is asynchronous): dispatch,
        # and where the process has not run this structure and shape yet,
        # trace, lower, cache lookup and executable load
        with tracing.span("trial.first_step") if s == 0 else nullcontext():
            state, loss, counters = programs.step_fn(state, tokens, sub, *schedule)
        eval_loss = None  # stale after this step's update
        if report is not None and (s % report_every == 0 or s == steps - 1):
            eval_loss = evaluate(s, first=s == 0)
            if report(step=s, loss=float(loss), eval_loss=eval_loss) is False:
                break
    if eval_loss is None:
        eval_loss = evaluate(steps - 1, first=True)  # nothing was reported
    return eval_loss


# -- the white-box trial function -------------------------------------------


def _block_sizes(cls, block: str, p, mesh) -> dict:
    """The fields of a block's sizes (``MlaMoeSizes`` | ``GqaMoeSizes`` |
    ``LoopedSizes``) from a trial's parameters: every field under its own
    name, a layout as a string of 0 and 1 (the experts held are two integers
    of their own: ``_expert_block_sizes``).  These blocks have no dropout and
    have not been run over a ``seq`` axis."""
    if mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1:
        raise ValueError(
            f"transformer_trial: block {block!r} cannot run on a mesh with a 'seq' axis: the ring "
            "and all-to-all attention paths assume keys and values of one width and one head "
            "count, have no window, and have run block 'gpt2' alone"
        )
    if float(p.get("dropout", 0.0)) > 0.0:
        raise ValueError(f"transformer_trial: block {block!r} has no dropout")
    sizes = {}
    for f in dataclasses.fields(cls):
        if f.name == "experts_held":
            continue
        if f.name.endswith("_layout"):
            raw = str(p.get(f.name, "".join(map(str, f.default))))
            if not raw or set(raw) - set("01"):
                raise ValueError(
                    f"transformer_trial: {f.name} {raw!r} is not a string of 0 and 1, one a layer kind"
                )
            sizes[f.name] = tuple(int(c) for c in raw)
        else:
            sizes[f.name] = type(f.default)(p.get(f.name, f.default))
    return sizes


def _expert_block_sizes(cls, block: str, p, mesh):
    """An expert block's sizes, with the experts held as (first, count)."""
    sizes = _block_sizes(cls, block, p, mesh)
    held = (
        int(p.get("experts_held_first", 0)),
        int(p.get("experts_held", sizes["n_experts"])),
    )
    if not 0 <= held[0] < held[0] + held[1] <= sizes["n_experts"]:
        raise ValueError(
            f"transformer_trial: experts held {held} (first, count) lie outside the "
            f"{sizes['n_experts']} routed experts"
        )
    return cls(experts_held=held, **sizes)


def _mla_moe_model(p, vocab: int, mesh) -> MlaMoeLM:
    """The ``block: mla_moe`` model from a trial's parameters."""
    return MlaMoeLM(
        vocab_size=vocab,
        sizes=_expert_block_sizes(MlaMoeSizes, MlaMoeLM.BLOCK, p, mesh),
        attn_fn=make_attention_fn(mesh),
    )


def _gqa_moe_model(p, vocab: int, mesh) -> GqaMoeLM:
    """The ``block: gqa_moe`` model from a trial's parameters.  No ``seq``
    axis, so each kind of layer runs the one-device attention (a ``data`` axis
    shards its batch as it does every other operation's)."""
    sizes = _expert_block_sizes(GqaMoeSizes, GqaMoeLM.BLOCK, p, mesh)
    if sizes.n_heads % sizes.n_kv_heads:
        raise ValueError(
            f"transformer_trial: {sizes.n_heads} query heads are not a multiple of "
            f"{sizes.n_kv_heads} key-value heads"
        )
    return GqaMoeLM(
        vocab_size=vocab,
        sizes=sizes,
        attn_fn=make_attention_fn(),
        window_attn_fn=make_attention_fn(window=sizes.window),
    )


def _looped_model(p, vocab: int, mesh) -> LoopedLM:
    """The ``block: looped`` model from a trial's parameters."""
    sizes = LoopedSizes(**_block_sizes(LoopedSizes, LoopedLM.BLOCK, p, mesh))
    if sizes.ut_steps < 1:
        raise ValueError(f"transformer_trial: ut_steps {sizes.ut_steps}: the stack runs at least once")
    return LoopedLM(vocab_size=vocab, sizes=sizes, attn_fn=make_attention_fn(mesh))


def _gpt2_model(p, vocab: int, mesh) -> TransformerLM:
    """The ``block: gpt2`` model from a trial's parameters."""
    return TransformerLM(
        vocab_size=vocab,
        d_model=int(p.get("d_model", 128)),
        n_heads=int(p.get("n_heads", 4)),
        n_layers=int(p.get("n_layers", 2)),
        max_seq_len=int(p.get("seq_len", 512)),
        dropout=float(p.get("dropout", 0.0)),
        attn_fn=make_attention_fn(mesh, strategy=str(p.get("attn", "ring"))),
    )


#: ``block`` -> (parameters, vocabulary, mesh) -> the model
_BLOCKS = {
    TransformerLM.BLOCK: _gpt2_model,
    MlaMoeLM.BLOCK: _mla_moe_model,
    GqaMoeLM.BLOCK: _gqa_moe_model,
    LoopedLM.BLOCK: _looped_model,
}


def transformer_trial(ctx) -> None:
    """White-box trial: tunable long-context LM reporting train/eval loss."""
    p = ctx.params
    vocab = int(p.get("vocab_size", 256))
    seq_len = int(p.get("seq_len", 512))
    mesh = ctx.mesh

    with tracing.span("trial.data"):
        block = str(p.get("block", "gpt2"))
        if block not in _BLOCKS:
            *known, last = map(repr, _BLOCKS)
            raise ValueError(
                f"transformer_trial: block {block!r} is neither {', '.join(known)} nor {last}"
            )
        model = _BLOCKS[block](p, vocab, mesh)
        data = markov_dataset(
            vocab, int(p.get("n_seq", 512)), seq_len, seed=int(p.get("data_seed", 0))
        )

    def report(step, loss, eval_loss):
        return ctx.report(step=step, loss=loss, eval_loss=eval_loss)

    train_lm(
        model,
        data,
        lr=float(p.get("lr", 3e-3)),
        steps=int(p.get("steps", 60)),
        batch_size=int(p.get("batch_size", 16)),
        warmup_frac=float(p.get("warmup_frac", 0.1)),
        mesh=mesh,
        report=report,
    )
