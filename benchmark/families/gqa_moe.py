"""Family ``gqa_moe``: grouped-query attention in a period of layer kinds
(a window with rotary positions, or the whole prefix with none) and sparse
experts routed from the layer's input, as ``SmallThinker-21BA3B-Instruct``
configures them, trained by ``transformer_trial`` with ``block: gqa_moe``.

What the family adds to the benchmark, beside what ``families/gpt2.py`` and
``families/mla_moe.py`` have:

- a configuration file whose top-level keys are the published ``config.json``'s
  own (``SIZE_KEYS``): ``experiment_doc`` turns them into the trial's
  parameters (``PARAMS``, ``FLOAT_PARAMS``, ``LAYOUT_PARAMS``).  The two
  layouts stay whole in the file (52 entries, as published); the layers that
  are run are the first ``num_hidden_layers`` of them, handed to the trial as
  one period (``"0111"``);
- ``moe_num_primary_experts`` there counts the experts HELD by this chip (the
  deployment's share: ``reduced``), ``router_width`` the experts routed over;
- the plain reference of the layer (``_layer_functions``), from the equations:
  router logits ``x W_r`` from the layer's input before any norm; RMSNorm;
  ``q``, ``k``, ``v`` with fewer key-value heads than query heads (query head
  ``j`` reads key-value head ``j // group``); on a layer whose ``rope_layout``
  is 1 rotary over the whole head, the halves paired; softmax over the keys
  ``t' <= t`` and, where ``sliding_window_layout`` is 1, ``t - t' < window``,
  in blocks of queries against all keys (no kernel, no skipped tile); RMSNorm;
  the 6 largest logits, a softmax over the chosen, ReLU-gated experts as a
  loop over the experts held (no sort, no grouped product); an untied head.
  What the absent experts would add is left out, as in the program.  It
  imports nothing of ``katib_tpu``;
- faults ``half_batch``, ``state_unchanged``, ``no_routed`` and, new here,
  ``no_window`` (the window layers attend to the whole prefix);
- ``flash_attention_cost`` and ``step_flops`` count the VISIBLE pairs of each
  kind of layer (``S(S+1)/2``, or ``W(W+1)/2 + (S-W)W`` under a window) and
  keys and values at their own head count, so the roofline reads the same
  work whatever implements it; ``expert_product_cost`` as in ``mla_moe``.

Departures from the published model, reproduced here: no auxiliary loss;
flax's default initialisers from ``PRNGKey(0)``; the "secondary experts" of the
model's description have no key in this config and are not built.  The program
computes in bfloat16 with float32 parameters, router logits, logits and loss;
the reference computes in float32 with ``highest`` matmul precision.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os


def _sibling(name: str):
    """Another family's file, loaded by path as ``run.py`` loads this one."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_families_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# All families train through ``transformer_trial`` and ``train_lm``: the
# trial's data, batches and schedule, the reports that are compared, the
# precision controls and ``compare`` are the gpt2 family's, not copies of them.
_gpt2 = _sibling("gpt2")
COMPARE_STEPS, METRICS, TRAIN_FN = _gpt2.COMPARE_STEPS, _gpt2.METRICS, _gpt2.TRAIN_FN
STEP_MODULE, EVAL_MODULE = _gpt2.STEP_MODULE, _gpt2.EVAL_MODULE
lr_values, compare = _gpt2.lr_values, _gpt2.compare
markov_tokens, batches, lr_at, _matmul = _gpt2.markov_tokens, _gpt2.batches, _gpt2.lr_at, _gpt2._matmul

BLOCK = "gqa_moe"
#: a ``tpu_custom_call`` names its operands' layouts, first operand first: the
#: attention kernels take q (bfloat16) first, the grouped products that
#: ``jax.lax.ragged_dot`` compiles to take int32 group metadata first
#: (families/mla_moe.py has the same two marks)
FLASH_KERNEL_MARK = "operand_layout_constraints={bf16["
EXPERT_PRODUCT_MARK = "operand_layout_constraints={s32["

#: the trial's integer parameters, by the configuration file's key
PARAMS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
    "sliding_window_size": "window",
    "moe_ffn_hidden_size": "expert_width",
    "router_width": "n_experts",
    "moe_num_active_primary_experts": "experts_per_token",
    "experts_held_first": "experts_held_first",
    "moe_num_primary_experts": "experts_held",
    "vocab_size": "vocab_size",
    "seq_len": "seq_len",
    "batch_size": "batch_size",
    "n_seq": "n_seq",
}
#: those that are not integers
FLOAT_PARAMS = {"rope_theta": "rope_theta", "rms_norm_eps": "eps"}
#: and the two lists of a layer's kind, one entry a layer
LAYOUT_PARAMS = {"sliding_window_layout": "window_layout", "rope_layout": "rope_layout"}
SIZE_KEYS = tuple(PARAMS) + tuple(FLOAT_PARAMS) + tuple(LAYOUT_PARAMS)
_NOT_SHAPE = ("seq_len", "batch_size", "n_seq")


def layer_kinds(sizes: dict) -> list[tuple[bool, bool]]:
    """(windowed, rotary) of every layer that is run: the layouts' first
    ``num_hidden_layers`` entries."""
    n = sizes["num_hidden_layers"]
    return [
        (bool(w), bool(r))
        for w, r in zip(sizes["sliding_window_layout"][:n], sizes["rope_layout"][:n], strict=True)
    ]


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  A program that has no
    such block would take ``block`` for a parameter it does not know and train
    GPT-2 blocks of these sizes: refuse it here, at once."""
    if importlib.util.find_spec("katib_tpu.models.gqa_moe") is None:
        raise SystemExit(
            "families/gqa_moe.py: this checkout's transformer_trial has no block 'gqa_moe' "
            "(katib_tpu/models/gqa_moe.py is missing): the configuration cannot run here"
        )

    def pinned(pname: str, value: int) -> dict:
        return {
            "name": pname,
            "parameterType": "int",
            "feasibleSpace": {"min": str(value), "max": str(value)},
        }

    def one_of(pname: str, kind: str, values) -> dict:
        return {"name": pname, "parameterType": kind, "feasibleSpace": {"list": [str(v) for v in values]}}

    params = []
    for p in traffic["parameters"]:
        p = dict(p)
        if p["name"] == "lr" and lr_values is not None:
            p["feasibleSpace"] = {"list": [str(v) for v in lr_values]}
        params.append(p)
    params.append(one_of("block", "categorical", [BLOCK]))
    params += [pinned(PARAMS[k], int(sizes[k])) for k in PARAMS]
    params += [one_of(FLOAT_PARAMS[k], "discrete", [float(sizes[k])]) for k in FLOAT_PARAMS]
    kinds = layer_kinds(sizes)
    for key, column in (("sliding_window_layout", 0), ("rope_layout", 1)):
        period = "".join(str(int(kind[column])) for kind in kinds)
        params.append(one_of(LAYOUT_PARAMS[key], "categorical", [period]))
    params += [pinned("steps", int(traffic["steps"])), pinned("data_seed", int(seed))]
    return {
        "apiVersion": "kubeflow.org/v1beta1",
        "kind": "Experiment",
        "metadata": {"name": name},
        "spec": {
            "objective": {
                "type": "minimize",
                "objectiveMetricName": "eval_loss",
                "additionalMetricNames": ["loss"],
            },
            "algorithm": {
                "algorithmName": traffic["algorithm"],
                "algorithmSettings": [{"name": "random_state", "value": str(int(seed))}],
            },
            "parallelTrialCount": int(traffic["parallelTrialCount"]),
            "maxTrialCount": int(max_trials or traffic["maxTrialCount"]),
            "maxFailedTrialCount": 0,
            "parameters": params,
            "trialTemplate": {"trainFn": TRAIN_FN},
        },
    }


# ---------------------------------------------------------------------------
# operations and bytes, from shapes and from the counted assignments
# ---------------------------------------------------------------------------


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_ffn_hidden_size"]


def matmul_params(sizes: dict) -> float:
    """Parameters that take part in a product for one token.  A layer's
    attention (q and the output at the query heads, k and v at the key-value
    heads), its router, and the routed experts a token EXPECTS here:
    ``moe_num_active_primary_experts`` times the share of the routed experts
    held (6 x 8/64 = 0.75, not 6); the head."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    attention = 2 * d * nh * hd + 2 * d * nkv * hd
    routed = (
        sizes["moe_num_active_primary_experts"] * sizes["moe_num_primary_experts"] / sizes["router_width"]
    )
    layer = attention + d * sizes["router_width"] + routed * expert_params(sizes)
    return sizes["num_hidden_layers"] * layer + d * sizes["vocab_size"]


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal layer sees: every ``t' <= t``, under a
    window those with ``t - t' < window``."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_flops_fwd(sizes: dict, windowed: bool) -> float:
    """QK^T and PV over the visible pairs of one layer, every query head."""
    pairs = visible_pairs(sizes["seq_len"], sizes["sliding_window_size"] if windowed else None)
    return 4.0 * sizes["batch_size"] * sizes["num_attention_heads"] * pairs * sizes["head_dim"]


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per product
    parameter and token, plus attention over the visible pairs of each layer
    (backward twice the forward).  Rematerialised blocks and the kernel's
    recomputation are not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    attention = sum(3.0 * attention_flops_fwd(sizes, windowed) for windowed, _ in layer_kinds(sizes))
    return 6.0 * matmul_params(sizes) * tokens + attention


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's attention, forward + backward,
    the MEAN over the layers that are run (``flash_attn_roofline`` multiplies
    by ``calls_per_step``; every kind of layer is bound by its operations, so
    the mean of the bounds is the bound of the means).  Operations over the
    visible pairs.  Bytes: forward reads q and writes o at the query heads,
    reads k and v at the key-value heads; backward reads q, o, do and writes
    dq, reads k, v and writes dk, dv (bfloat16), plus the float32 log-sum-exp
    written once and read once."""
    b, s, hd = sizes["batch_size"], sizes["seq_len"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    kinds = layer_kinds(sizes)
    row_q, row_kv = b * nh * s * hd * 2, b * nkv * s * hd * 2
    lse = b * nh * s * 4
    return {
        "flops": sum(3.0 * attention_flops_fwd(sizes, windowed) for windowed, _ in kinds) / len(kinds),
        "bytes": float((2 * row_q + 2 * row_kv) + (4 * row_q + 4 * row_kv) + 2 * lse),
        "calls_per_step": len(kinds),
    }


def expert_product_cost(sizes: dict, assignments_held: float) -> dict:
    """Operations and HBM bytes of one step's grouped products over the
    experts held, forward + backward, from the COUNTED assignments to them
    (all layers together): as ``families/mla_moe.py`` counts them."""
    d, w = sizes["hidden_size"], sizes["moe_ffn_hidden_size"]
    weights = sizes["num_hidden_layers"] * sizes["moe_num_primary_experts"] * expert_params(sizes)
    rows_forward = (d * 2 + 2 * w * 4) + (w * 2 + d * 4)
    return {
        "flops": 6.0 * expert_params(sizes) * assignments_held,
        "bytes": float(weights * (2 + 2 + 4) + 3 * rows_forward * assignments_held),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def shape_of(sizes: dict) -> tuple:
    """What shapes the weights and the reference's programs, hashable: the
    sizes, and the kinds of the layers that are run."""
    scalars = tuple(sizes[k] for k in tuple(PARAMS) + tuple(FLOAT_PARAMS) if k not in _NOT_SHAPE)
    return scalars + (tuple(layer_kinds(sizes)),)


def _named(shape: tuple) -> dict:
    keys = [k for k in tuple(PARAMS) + tuple(FLOAT_PARAMS) if k not in _NOT_SHAPE]
    return dict(zip(keys + ["kinds"], shape, strict=True))


def init_params(sizes: dict):
    return _init_program(shape_of(sizes))()


@functools.lru_cache(maxsize=None)
def _init_program(shape: tuple):
    """Initial weights as flax draws them from ``PRNGKey(0)`` for modules of
    the program's names, shapes, initialisers and order of declaration (the
    trial's seed never reaches its weights).  The skeleton below only declares
    the parameters; the reference's arithmetic is ``_forward``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    d, hd = z["hidden_size"], z["head_dim"]
    nh, nkv = z["num_attention_heads"], z["num_key_value_heads"]
    width, held = z["moe_ffn_hidden_size"], z["moe_num_primary_experts"]
    dense = functools.partial(nn.Dense, use_bias=False)

    class Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense(nh * hd, name="q_proj")(x)
            dense(nkv * hd, name="k_proj")(x)
            dense(nkv * hd, name="v_proj")(x)
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (nh * hd,)))

    class Experts(nn.Module):
        @nn.compact
        def __call__(self, x):
            stacked = nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", batch_axis=(0,)
            )
            self.param("experts_gate", stacked, (held, d, width))
            self.param("experts_up", stacked, (held, d, width))
            self.param("experts_down", stacked, (held, width, d))
            return x

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("router", nn.initializers.lecun_normal(), (d, z["router_width"]))
            Norm(name="input_norm")(x)
            Attention(name="attn")(x)
            Norm(name="post_attn_norm")(x)
            return Experts(name="moe")(x)

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(z["vocab_size"], d, name="embed")(tokens)
            for i in range(len(z["kinds"])):
                x = Layer(name=f"layer_{i}")(x)
            Norm(name="norm")(x)
            return dense(z["vocab_size"], name="head")(x)

    @jax.jit
    def make():
        tree = LM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        layers = []
        for i in range(len(z["kinds"])):
            layer = tree[f"layer_{i}"]
            layers.append(
                {
                    "router": layer["router"],
                    "norm1": layer["input_norm"]["scale"],
                    "norm2": layer["post_attn_norm"]["scale"],
                    **{n: layer["attn"][f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "o")},
                    **layer["moe"],
                }
            )
        return {
            "embed": tree["embed"]["embedding"],
            "norm": tree["norm"]["scale"],
            "head": tree["head"]["kernel"],
            "layers": layers,
        }

    return make


def _divisor_at_most(n: int, limit: float) -> int:
    r = max(1, min(n, int(limit)))
    while n % r:
        r -= 1
    return r


def _layer_functions(shape: tuple, precision: str, fault: str | None, r: int, s: int) -> dict:
    """The layer's parts for ``r`` rows of ``s`` positions, as plain functions
    of the residual stream ``x`` [R, S, D] and one layer's weights ``w``:
    ``router`` (the logits, from ``x`` as it is), ``attention(x, w, windowed,
    rope)`` (x + attention of the normed x) and ``moe(x, w, logits)`` (x + the
    held experts' part of the normed x)."""
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    mm = _matmul(precision)
    hd, nh, nkv = z["head_dim"], z["num_attention_heads"], z["num_key_value_heads"]
    group = nh // nkv
    window = z["sliding_window_size"]
    first, held, top = z["experts_held_first"], z["moe_num_primary_experts"], z["moe_num_active_primary_experts"]
    eps = z["rms_norm_eps"]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    # rotary over the whole head: the pair (x[i], x[i + hd/2]) turns by
    # pos * theta^(-2i/hd)
    inv_freq = z["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]  # [S, 1, hd/2]

    def rotate(x):  # [R, S, H, hd]
        a, b = x[..., : hd // 2], x[..., hd // 2 :]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    # queries whose scores against all keys are live at once: about 0.27 GB
    q_block = _divisor_at_most(s, 0.27e9 // (r * nh * s * 4))
    key_pos = jnp.arange(s)

    def attention(x, w, windowed: bool, rope: bool):
        h = rms_norm(x, w["norm1"])
        q = mm("rsd,de->rse", h, w["q"]).reshape(r, s, nh, hd)
        k = mm("rsd,de->rse", h, w["k"]).reshape(r, s, nkv, hd)
        v = mm("rsd,de->rse", h, w["v"]).reshape(r, s, nkv, hd)
        if rope:
            q, k = rotate(q), rotate(k)
        if fault == "no_window":
            windowed = False

        @jax.checkpoint
        def attend(block):
            q_blk, t0 = block  # [R, q_block, Hkv, group, hd]: a key-value head's query heads
            t = t0 + jnp.arange(q_block)[:, None]
            seen = key_pos[None, :] <= t
            if windowed:
                seen &= t - key_pos[None, :] < window
            scores = mm("rqngd,rknd->rngqk", q_blk, k) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm("rngqk,rknd->rqngd", probs, v)

        blocks = q.reshape(r, s // q_block, q_block, nkv, group, hd).swapaxes(0, 1)
        o = jax.lax.map(attend, (blocks, jnp.arange(0, s, q_block)))
        o = o.swapaxes(0, 1).reshape(r, s, nh * hd)
        return x + mm("rse,ed->rsd", o, w["o"])

    def router(x, w):
        return mm("rsd,de->rse", x, w["router"])  # over ALL experts

    def moe(x, w, logits):
        if fault == "no_routed":
            return x
        h = rms_norm(x, w["norm2"])
        chosen_logits, chosen = jax.lax.top_k(logits, top)
        weights = jax.nn.softmax(chosen_logits, axis=-1)
        # [R, S, held]: a chosen expert's weight where it is held here, else 0
        # (one_hot of an index outside [0, held) is a row of zeros)
        per_expert = jnp.sum(jax.nn.one_hot(chosen - first, held) * weights[..., None], axis=-2)

        @jax.checkpoint
        def add_expert(acc, e):
            gate, up, down, weight = e
            hidden = jax.nn.relu(mm("...d,de->...e", h, gate)) * mm("...d,de->...e", h, up)
            return acc + weight[..., None] * mm("...e,ed->...d", hidden, down), None

        routed, _ = jax.lax.scan(
            add_expert,
            jnp.zeros_like(x),
            (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(per_expert, -1, 0)),
        )
        return x + routed

    return {"rms_norm": rms_norm, "router": router, "attention": attention, "moe": moe, "mm": mm}


def _stream(params, tokens, shape: tuple, precision: str, fault: str | None):
    """The residual stream of ``tokens`` [R, S] after the last norm, [R, S, D],
    and the layer's functions it was computed with."""
    import jax

    f = _layer_functions(shape, precision, fault, *tokens.shape)

    def layer(x, w, windowed, rope):
        logits = f["router"](x, w)  # from the layer's input, before attention
        return f["moe"](f["attention"](x, w, windowed, rope), w, logits)

    x = params["embed"][tokens]
    for w, (windowed, rope) in zip(params["layers"], _named(shape)["kinds"], strict=True):
        x = jax.checkpoint(functools.partial(layer, windowed=windowed, rope=rope))(x, w)
    return f["rms_norm"](x, params["norm"]), f


def _forward(params, tokens, shape: tuple, precision: str, fault: str | None):
    """Mean next-token cross entropy of ``tokens`` [R, S]; the head and the
    loss in blocks of positions (a block's float32 logits near 0.3 GB)."""
    import jax
    import jax.numpy as jnp

    r, s = tokens.shape
    x, f = _stream(params, tokens, shape, precision, fault)
    # position t predicts token t+1; the last position predicts nothing
    targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((r, 1), tokens.dtype)], axis=1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
    block = _divisor_at_most(s, 0.3e9 // (r * params["head"].shape[1] * 4))

    @jax.checkpoint
    def block_nll(args):
        x_blk, target, weight = args  # [R, block, D], [R, block], [block]
        logits = f["mm"]("rsd,dv->rsv", x_blk, params["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weight)

    split = lambda a: jnp.moveaxis(a.reshape(r, s // block, block, *a.shape[2:]), 1, 0)  # noqa: E731
    sums = jax.lax.map(block_nll, (split(x), split(targets), counted.reshape(-1, block)))
    return jnp.sum(sums) / (r * (s - 1))


@functools.lru_cache(maxsize=None)
def _programs(shape: tuple, rows_per_block: int, precision: str, fault: str | None):
    """The reference's two jitted programs: one train step (loss and gradient
    in blocks of rows, then clip and AdamW) and the eval loss.  ``fault``
    plants one of the faults the correctness tests must see (never set by a
    benchmark run)."""
    import jax
    import jax.numpy as jnp

    def blocks_of(tokens):
        r = math.gcd(rows_per_block, tokens.shape[0])
        return tokens.reshape(-1, r, tokens.shape[1])

    def loss_of(params, tokens):
        return _forward(params, tokens, shape, precision, fault)

    @jax.jit
    def eval_loss(params, tokens):
        return jnp.mean(jax.lax.map(lambda t: loss_of(params, t), blocks_of(tokens)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, lr, tokens):
        if fault == "half_batch":
            # half the rows; of a single row, the first half of its positions
            rows, s = tokens.shape
            tokens = tokens[: rows // 2] if rows > 1 else tokens[:, : s // 2]
        blocks = blocks_of(tokens)

        def one(acc, t):
            loss, g = jax.value_and_grad(loss_of)(params, t)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss

        if blocks.shape[0] == 1:  # no second copy of the gradients
            loss, grads = jax.value_and_grad(loss_of)(params, blocks[0])
            losses = loss[None]
        else:
            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            gsum, losses = jax.lax.scan(one, zero, blocks)
            grads = jax.tree_util.tree_map(lambda g: g / blocks.shape[0], gsum)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
        clip = jnp.minimum(1.0, 1.0 / (gnorm + 1e-6))
        t = (count + 1).astype(jnp.float32)
        c1, c2 = 1.0 - 0.9**t, 1.0 - 0.999**t

        def adamw(p, g, m, v):
            g = g * clip
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * jnp.square(g)
            p_new = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + 1e-8) + 0.01 * p)
            return p_new, m, v

        out = jax.tree_util.tree_map(adamw, params, grads, m, v)
        pick = lambda i: jax.tree_util.tree_map(lambda _, o: o[i], params, out)  # noqa: E731
        new_params = params if fault == "state_unchanged" else pick(0)
        return new_params, pick(1), pick(2), jnp.mean(losses), gnorm

    return step, eval_loss


def rows_per_block(sizes: dict) -> int:
    """Rows the reference differentiates at once: a row's layer activations in
    float32 (the scores are blocked by queries and the logits by positions
    inside ``_forward``); keep a block near 2 GB.  At the cell's sizes that is
    the one row."""
    per_row = sizes["seq_len"] * 4 * 12 * sizes["num_attention_heads"] * sizes["head_dim"]
    return _divisor_at_most(sizes["batch_size"], 2e9 // per_row)


def reference_series(
    sizes: dict, traffic: dict, seed: int, lr: float, *, precision: str = "f32", fault: str | None = None
) -> dict:
    """``{"loss": {step: value}, "eval_loss": {step: value}}`` of one trial's
    first reports, computed by the plain reference."""
    import jax
    import jax.numpy as jnp

    steps = int(traffic["steps"])
    last = max(COMPARE_STEPS)
    data = markov_tokens(sizes["vocab_size"], sizes["n_seq"], sizes["seq_len"], seed)
    rows, eval_rows = batches(data, sizes["batch_size"], last + 1)
    step, eval_loss = _programs(shape_of(sizes), rows_per_block(sizes), precision, fault)
    params = init_params(sizes)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    eval_tokens = jnp.asarray(eval_rows)
    out = {"loss": {}, "eval_loss": {}}
    for s in range(last + 1):
        params, m, v, loss, _ = step(
            params, m, v, jnp.int32(s), jnp.float32(lr_at(s, lr, steps)), jnp.asarray(rows[s])
        )
        if s in COMPARE_STEPS:
            out["loss"][s] = float(loss)
            out["eval_loss"][s] = float(eval_loss(params, eval_tokens))
    del params, m, v
    return out
