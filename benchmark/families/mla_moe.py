"""Family ``mla_moe``: latent attention and sparse experts (DeepSeek-V3's
layer, as ``kanana-2-30b-a3b-instruct-2601`` configures it) trained by
``transformer_trial`` with ``block: mla_moe``.

What the family adds to the benchmark, beside what ``families/gpt2.py`` has:

- a configuration file whose top-level keys are the published ``config.json``'s
  own (``SIZE_KEYS``): ``experiment_doc`` turns them into the trial's
  parameters (``PARAMS``), so the file is read as the model's config is;
- ``n_routed_experts`` there counts the experts HELD by this chip (the
  deployment's share: ``reduced``), ``router_width`` the experts routed over;
- the plain reference of the layer (``_forward``): RMSNorm, rotary pairs
  interleaved, latent keys and values, causal softmax attention, SwiGLU,
  sigmoid router over all experts, the largest chosen, weights normalised
  over the chosen and scaled, the routed sum as a loop over the experts held
  (no sort, no grouped product, no kernel), shared experts, untied head.  What
  the absent experts would add is left out, as in the program.  It imports
  nothing of ``katib_tpu``;
- one fault more: ``no_routed`` (the routed sum left out);
- ``expert_product_cost``: operations and bytes of the grouped products from
  the counted assignments, and ``EXPERT_PRODUCT_MARK``, which finds their
  kernels in the device trace.

Departures from the published model, reproduced here: the router's
``e_score_correction_bias`` is a buffer held at zero (left out); no auxiliary
loss; flax's default initialisers from ``PRNGKey(0)``.  The program computes
in bfloat16 with float32 parameters, router scores, logits and loss; the
reference computes in float32 with ``highest`` matmul precision.
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


# Both families train through ``transformer_trial`` and ``train_lm``: the
# trial's data, batches and schedule, the reports that are compared, the
# precision controls and ``compare`` are the gpt2 family's, not copies of them.
_gpt2 = _sibling("gpt2")
COMPARE_STEPS, METRICS, TRAIN_FN = _gpt2.COMPARE_STEPS, _gpt2.METRICS, _gpt2.TRAIN_FN
STEP_MODULE, EVAL_MODULE = _gpt2.STEP_MODULE, _gpt2.EVAL_MODULE
lr_values, compare = _gpt2.lr_values, _gpt2.compare
markov_tokens, batches, lr_at, _matmul = _gpt2.markov_tokens, _gpt2.batches, _gpt2.lr_at, _gpt2._matmul

BLOCK = "mla_moe"
#: a ``tpu_custom_call`` names its operands' layouts, first operand first: the
#: attention kernels of ops/flash_attention.py take q (bfloat16) first, the
#: grouped products that ``jax.lax.ragged_dot`` compiles to (forward, and the
#: two of its transpose) and the kernel that lays out their groups take
#: int32 group metadata first
FLASH_KERNEL_MARK = "operand_layout_constraints={bf16["
EXPERT_PRODUCT_MARK = "operand_layout_constraints={s32["

#: the trial's integer parameters, by the configuration file's key
PARAMS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_hidden_layers": "n_layers",
    "first_k_dense_replace": "first_dense_layers",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "kv_lora_rank": "kv_lora_rank",
    "intermediate_size": "dense_width",
    "moe_intermediate_size": "expert_width",
    "router_width": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "n_shared_experts",
    "experts_held_first": "experts_held_first",
    "n_routed_experts": "experts_held",
    "vocab_size": "vocab_size",
    "seq_len": "seq_len",
    "batch_size": "batch_size",
    "n_seq": "n_seq",
}
#: and those that are not integers
FLOAT_PARAMS = {
    "routed_scaling_factor": "routed_scaling",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "eps",
}
SIZE_KEYS = tuple(PARAMS) + tuple(FLOAT_PARAMS)


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  A program that has no
    such block would take ``block`` for a parameter it does not know and train
    GPT-2 blocks of these sizes: refuse it here, at once."""
    if importlib.util.find_spec("katib_tpu.models.mla_moe") is None:
        raise SystemExit(
            "families/mla_moe.py: this checkout's transformer_trial has no block 'mla_moe' "
            "(katib_tpu/models/mla_moe.py is missing): the configuration cannot run here"
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


def _expert_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["first_k_dense_replace"]


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def matmul_params(sizes: dict) -> float:
    """Parameters that take part in a product for one token.  A layer's
    attention (q, the compressed kv, its up-projection, the output); a dense
    layer's SwiGLU; an expert layer's router, shared experts and the routed
    experts a token EXPECTS here: ``num_experts_per_tok`` times the share of
    the routed experts held (6 x 16/128 = 0.75, not 6); the head."""
    d, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    rank = sizes["kv_lora_rank"]
    attention = d * nh * (nope + rope) + d * (rank + rope) + rank * nh * (nope + dv) + nh * dv * d
    dense = 3 * d * sizes["intermediate_size"]
    routed = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / sizes["router_width"]
    expert = (
        d * sizes["router_width"]
        + sizes["n_shared_experts"] * expert_params(sizes)
        + routed * expert_params(sizes)
    )
    return (
        sizes["num_hidden_layers"] * attention
        + sizes["first_k_dense_replace"] * dense
        + _expert_layers(sizes) * expert
        + d * sizes["vocab_size"]
    )


def attention_flops_fwd(sizes: dict) -> float:
    """QK^T over the keys' width and PV over the values', the causal half."""
    b, h, s = sizes["batch_size"], sizes["num_attention_heads"], sizes["seq_len"]
    width = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"] + sizes["v_head_dim"]
    return float(b * h * s * s * width)


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per product
    parameter and token, plus causal attention in every layer (backward twice
    the forward).  Rematerialised blocks and the kernel's recomputation are
    not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    attention = 3.0 * attention_flops_fwd(sizes) * sizes["num_hidden_layers"]
    return 6.0 * matmul_params(sizes) * tokens + attention


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's attention, forward + backward:
    forward reads q, k (keys' width) and v and writes o (values' width);
    backward reads q, k, v, o, do and writes dq, dk, dv (bfloat16), plus the
    float32 log-sum-exp written once and read once."""
    b, h, s = sizes["batch_size"], sizes["num_attention_heads"], sizes["seq_len"]
    dk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    row = b * h * s * 2
    lse = b * h * s * 4
    forward = row * (2 * dk + 2 * dv)
    backward = row * (2 * dk + 3 * dv) + row * (2 * dk + dv)
    return {
        "flops": 3.0 * attention_flops_fwd(sizes),
        "bytes": float(forward + backward + 2 * lse),
        "calls_per_step": sizes["num_hidden_layers"],
    }


def expert_product_cost(sizes: dict, assignments_held: float) -> dict:
    """Operations and HBM bytes of one step's grouped products over the
    experts held, forward + backward, from the COUNTED assignments to them
    (all expert layers together).  Operations: gate, up and down, 2 an
    assignment and parameter forward, 4 backward.  Bytes: every held expert's
    weights read in bfloat16 forward and backward and their float32 gradients
    written, a layer; the gathered rows in (bfloat16) and the products out
    (float32) forward, and as much again twice backward."""
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    weights = _expert_layers(sizes) * sizes["n_routed_experts"] * expert_params(sizes)
    rows_forward = (d * 2 + 2 * w * 4) + (w * 2 + d * 4)
    return {
        "flops": 6.0 * expert_params(sizes) * assignments_held,
        "bytes": float(weights * (2 + 2 + 4) + 3 * rows_forward * assignments_held),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def shape_of(sizes: dict) -> tuple:
    """What shapes the weights and the reference's programs, hashable."""
    return tuple(sizes[k] for k in SIZE_KEYS if k not in ("seq_len", "batch_size", "n_seq"))


def _named(shape: tuple) -> dict:
    keys = [k for k in SIZE_KEYS if k not in ("seq_len", "batch_size", "n_seq")]
    return dict(zip(keys, shape))


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
    d, nh = z["hidden_size"], z["num_attention_heads"]
    nope, rope, dv, rank = z["qk_nope_head_dim"], z["qk_rope_head_dim"], z["v_head_dim"], z["kv_lora_rank"]
    width, held = z["moe_intermediate_size"], z["n_routed_experts"]
    n_layers, n_dense = z["num_hidden_layers"], z["first_k_dense_replace"]
    dense = functools.partial(nn.Dense, use_bias=False)

    class Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense(nh * (nope + rope), name="q_proj")(x)
            c = dense(rank + rope, name="kv_a_proj")(x)
            Norm(name="kv_norm")(c[..., :rank])
            dense(nh * (nope + dv), name="kv_b_proj")(c[..., :rank])
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (nh * dv,)))

    # (no module below has a field: a module loaded by path is not in
    # sys.modules, where the dataclass machinery looks annotations up)
    def swiglu(mlp_width: int):
        class SwiGLU(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = dense(mlp_width, name="gate_proj")(x)
                dense(mlp_width, name="up_proj")(x)
                return dense(x.shape[-1], name="down_proj")(h)

        return SwiGLU

    class Experts(nn.Module):
        @nn.compact
        def __call__(self, x):
            stacked = nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal", batch_axis=(0,)
            )
            self.param("router", nn.initializers.lecun_normal(), (d, z["router_width"]))
            self.param("experts_gate", stacked, (held, d, width))
            self.param("experts_up", stacked, (held, d, width))
            self.param("experts_down", stacked, (held, width, d))
            return swiglu(width * z["n_shared_experts"])(name="shared")(x)

    def layer(mlp):
        class Layer(nn.Module):
            @nn.compact
            def __call__(self, x):
                Norm(name="input_norm")(x)
                Attention(name="attn")(x)
                Norm(name="post_attn_norm")(x)
                return mlp(x)

        return Layer

    dense_layer = layer(lambda x: swiglu(z["intermediate_size"])(name="mlp")(x))
    expert_layer = layer(lambda x: Experts(name="moe")(x))

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(z["vocab_size"], d, name="embed")(tokens)
            for i in range(n_layers):
                x = (dense_layer if i < n_dense else expert_layer)(name=f"layer_{i}")(x)
            Norm(name="norm")(x)
            return dense(z["vocab_size"], name="head")(x)

    def attention_of(layer):
        a = layer["attn"]
        return {
            "norm1": layer["input_norm"]["scale"],
            "norm2": layer["post_attn_norm"]["scale"],
            "q": a["q_proj"]["kernel"],
            "kv_a": a["kv_a_proj"]["kernel"],
            "kv_norm": a["kv_norm"]["scale"],
            "kv_b": a["kv_b_proj"]["kernel"],
            "o": a["o_proj"]["kernel"],
        }

    def swiglu_of(node, prefix):
        return {
            prefix + "gate": node["gate_proj"]["kernel"],
            prefix + "up": node["up_proj"]["kernel"],
            prefix + "down": node["down_proj"]["kernel"],
        }

    def stack(layers):
        return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *layers)

    @jax.jit
    def make():
        tree = LM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        dense_layers, expert_layers = [], []
        for i in range(n_layers):
            layer = tree[f"layer_{i}"]
            if i < n_dense:
                dense_layers.append({**attention_of(layer), **swiglu_of(layer["mlp"], "")})
            else:
                moe = layer["moe"]
                expert_layers.append(
                    {
                        **attention_of(layer),
                        **swiglu_of(moe["shared"], "shared_"),
                        "router": moe["router"],
                        "experts_gate": moe["experts_gate"],
                        "experts_up": moe["experts_up"],
                        "experts_down": moe["experts_down"],
                    }
                )
        out = {
            "embed": tree["embed"]["embedding"],
            "norm": tree["norm"]["scale"],
            "head": tree["head"]["kernel"],
            "expert_layers": stack(expert_layers),
        }
        if dense_layers:
            out["dense_layers"] = stack(dense_layers)
        return out

    return make


def _layer_functions(shape: tuple, precision: str, fault: str | None, r: int, s: int) -> dict:
    """The layer's parts for ``r`` rows of ``s`` positions, as plain functions
    of the residual stream ``x`` [R, S, D] and one layer's weights ``w``:
    ``attention`` (x + attention of the normed x), ``dense_mlp`` and ``moe``
    (x + the MLP, or the shared and the held routed experts, of the normed x)."""
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    mm = _matmul(precision)
    nh = z["num_attention_heads"]
    nope, rope, dv, rank = z["qk_nope_head_dim"], z["qk_rope_head_dim"], z["v_head_dim"], z["kv_lora_rank"]
    first, held, top = z["experts_held_first"], z["n_routed_experts"], z["num_experts_per_tok"]
    eps = z["rms_norm_eps"]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    def silu(x):
        return x * jax.nn.sigmoid(x)

    # rotary: the pair (x[2i], x[2i+1]) turns by pos * theta^(-2i/rope)
    inv_freq = z["rope_theta"] ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]  # [S, 1, rope/2]

    def rotate(x):  # [R, S, H, rope]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    causal = jnp.tril(jnp.ones((s, s), bool))
    # heads whose scores are live at once: a block of about 0.27 GB
    group = max(1, min(r * nh, int(0.27e9 // (s * s * 4))))
    while (r * nh) % group:
        group -= 1

    @jax.checkpoint
    def attend(qkv):
        q, k, v = qkv  # [G, S, width]
        scores = mm("gqd,gkd->gqk", q, k) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return mm("gqk,gkd->gqd", probs, v)

    def attention(x, w):
        h = rms_norm(x, w["norm1"])
        q = mm("rsd,de->rse", h, w["q"]).reshape(r, s, nh, nope + rope)
        c = mm("rsd,de->rse", h, w["kv_a"])
        c_kv = rms_norm(c[..., :rank], w["kv_norm"])
        kv = mm("rsc,ce->rse", c_kv, w["kv_b"]).reshape(r, s, nh, nope + dv)
        k_rope = jnp.broadcast_to(rotate(c[..., None, rank:]), (r, s, nh, rope))
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        grouped = lambda t: t.transpose(0, 2, 1, 3).reshape(-1, group, s, t.shape[-1])  # noqa: E731
        o = jax.lax.map(attend, (grouped(q), grouped(k), grouped(kv[..., nope:])))
        o = o.reshape(r, nh, s, dv).transpose(0, 2, 1, 3).reshape(r, s, nh * dv)
        return x + mm("rse,ed->rsd", o, w["o"])

    def swiglu(h, gate, up, down):
        return mm("...e,ed->...d", silu(mm("...d,de->...e", h, gate)) * mm("...d,de->...e", h, up), down)

    def dense_mlp(x, w):
        return x + swiglu(rms_norm(x, w["norm2"]), w["gate"], w["up"], w["down"])

    def moe(x, w):
        h = rms_norm(x, w["norm2"])
        out = x + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        if fault == "no_routed":
            return out
        scores = jax.nn.sigmoid(mm("rsd,de->rse", h, w["router"]))  # over ALL experts
        chosen_scores, chosen = jax.lax.top_k(scores, top)
        weights = z["routed_scaling_factor"] * chosen_scores / (
            jnp.sum(chosen_scores, -1, keepdims=True) + 1e-20
        )
        # [R, S, held]: a chosen expert's weight where it is held here, else 0
        # (one_hot of an index outside [0, held) is a row of zeros)
        per_expert = jnp.sum(jax.nn.one_hot(chosen - first, held) * weights[..., None], axis=-2)

        @jax.checkpoint
        def add_expert(acc, e):
            gate, up, down, weight = e
            return acc + weight[..., None] * swiglu(h, gate, up, down), None

        routed, _ = jax.lax.scan(
            add_expert,
            jnp.zeros_like(x),
            (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(per_expert, -1, 0)),
        )
        return out + routed

    return {"rms_norm": rms_norm, "attention": attention, "dense_mlp": dense_mlp, "moe": moe, "mm": mm}


def _forward(params, tokens, shape: tuple, precision: str, fault: str | None):
    """Mean next-token cross entropy of ``tokens`` [R, S]."""
    import jax
    import jax.numpy as jnp

    f = _layer_functions(shape, precision, fault, *tokens.shape)

    @jax.checkpoint
    def dense_layer(x, w):
        return f["dense_mlp"](f["attention"](x, w), w), None

    @jax.checkpoint
    def expert_layer(x, w):
        return f["moe"](f["attention"](x, w), w), None

    x = params["embed"][tokens]
    if "dense_layers" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(expert_layer, x, params["expert_layers"])
    x = f["rms_norm"](x, params["norm"])
    logits = f["mm"]("rsd,dv->rsv", x, params["head"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


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
            tokens = tokens[: tokens.shape[0] // 2]
        blocks = blocks_of(tokens)

        def one(acc, t):
            loss, g = jax.value_and_grad(loss_of)(params, t)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        gsum, losses = jax.lax.scan(one, zero, blocks)
        n = blocks.shape[0]
        grads = jax.tree_util.tree_map(lambda g: g / n, gsum)
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
    """Rows the reference differentiates at once: a row's float32 logits and
    one layer's float32 activations (attention's scores are blocked by heads
    inside ``_forward``); keep a block near 2 GB.  At the cell's sizes that is
    the whole batch, so the gradients are not summed into a second copy: with
    576M parameters, their two moments and one gradient (9.2 GB) a second
    gradient would not fit."""
    per_row = sizes["seq_len"] * 4 * (
        sizes["vocab_size"] + 8 * sizes["num_attention_heads"] * sizes["v_head_dim"]
    )
    r = max(1, int(2e9 // per_row))
    while sizes["batch_size"] % r:
        r -= 1
    return r


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
