"""Family ``kda_moe``: Kimi delta attention (the gated delta rule with one
decay per channel and negative eigenvalues) in three layers of every four,
gated grouped-query attention without positions in the fourth, sparse experts
beside a shared one, as ``Solar-Open2-250B`` configures them, trained by
``transformer_trial`` with ``block: kda_moe``.

What the family adds to the benchmark, beside what the five older families
have:

- a configuration file whose top-level keys are the published ``config.json``'s
  own (``SIZE_KEYS``; the delta rule's sizes are in its nested
  ``linear_attn_config``): ``experiment_doc`` turns them into the trial's
  parameters.  ``n_routed_experts`` there counts the experts HELD by this chip,
  ``num_attention_heads``, ``num_key_value_heads`` and
  ``linear_attn_config.num_heads`` the heads it holds (the deployment's share:
  ``reduced``), ``router_width`` the experts routed over;
- the plain reference (``_layer_functions``), from the equations, ``RN`` an
  RMSNorm with weights only, no bias on any product, ``h = RN_1(x)``:
  on layer ``l`` with ``l mod (gqa_interval + 1) == 0`` (the published
  ``gqa_layers``) grouped-query attention without positions, a softmax over
  the keys ``s <= t`` in blocks of queries, its output times ``sigmoid(h
  W_gate)``, then ``W_o``; on the others the delta rule: ``q, k, v =
  SiLU(conv4(h W))`` (a causal depthwise convolution of four taps, no bias),
  ``q / |q| / sqrt(128)``, ``k / |k|``, ``g = -exp(A) softplus(h W_fa W_fb +
  b_dt)`` a channel, ``beta = 2 sigmoid(h w_b)``, then **token by token**
  ``S_t = (I - beta k k^T) Diag(e^g) S_{t-1} + beta k v^T``, ``o_t = S_t^T
  q_t`` (a ``lax.scan`` over the positions, rematerialised in blocks of 64
  so that its training step fits the chip at 4096 positions: no chunk
  algorithm), ``RN_o(o) sigmoid(h W_ga W_gb)`` then ``W_o``;
  experts: the router on ``RN_2(x')`` over all ``router_width`` experts in
  float32, the 8 largest sigmoid scores normalised over the chosen,
  SiLU-gated experts of ``moe_intermediate_size`` as a ``lax.scan`` over the
  experts held, every token weighted by its gate (0 where not routed), and
  the shared expert over every token; an untied head in blocks of positions.
  It imports nothing of ``katib_tpu``;
- faults ``half_batch``, ``state_unchanged`` and, new here, ``beta_one``
  (the step without its factor 2: no negative eigenvalue), ``decay_per_head``
  (one decay a head, the mean of its channels'), ``no_gate`` (neither layer
  kind's output gate) and ``state_bf16`` (the delta rule's state rounded to
  bfloat16 after every token);
- ``step_flops``, ``flash_attention_cost`` (the grouped-query layer alone),
  ``expert_product_cost`` and ``kda_scan_cost`` (the chunk algorithm's
  operations at chunk 64, written out once, and the bytes of its operands);
  ``KDA_MARK`` finds the scan's kernels in the device trace.

Departures from the published model, reproduced here: no auxiliary loss;
flax's default initialisers from ``PRNGKey(0)`` but ``A`` and ``b_dt``, drawn
as Kimi Linear draws them; no dense MLP (``first_k_dense_replace`` 0).  The
program computes in bfloat16 with float32 parameters, decay, step, the scan's
state, router scores, logits and loss; the reference computes in float32 with
``highest`` matmul precision.
"""

from __future__ import annotations

import functools
import importlib.util
import itertools
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

BLOCK = "kda_moe"
#: a ``tpu_custom_call`` names its operands' layouts, first operand first:
#: the attention kernels take q (bfloat16) first, the grouped products int32
#: group metadata, the delta rule's kernels a four-dimensional float32 array
#: (``ops/delta_rule.py``; a regular expression)
FLASH_KERNEL_MARK = "operand_layout_constraints={bf16["
EXPERT_PRODUCT_MARK = "operand_layout_constraints={s32["
KDA_MARK = r"operand_layout_constraints=\{f32\[\d+,\d+,\d+,\d+\]"
#: positions a chunk of the scan's kernels
CHUNK = 64

#: the trial's integer parameters, by the configuration file's key
PARAMS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "num_hidden_layers": "n_layers",
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
#: those that are not integers
FLOAT_PARAMS = {"rms_norm_eps": "eps"}
#: the delta rule's, from ``linear_attn_config``
KDA_PARAMS = {"num_heads": "kda_heads", "head_dim": "kda_head_dim", "short_conv_kernel_size": "conv_size"}
#: read and checked, and no parameter of the trial: what the block builds and
#: nothing else
CHECKED = {
    "use_rope": False,
    "first_k_dense_replace": 0,
    "use_gqa_gate": True,
    "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True,
    "norm_topk_prob": True,
    "routed_scaling_factor": 1,
    "tie_word_embeddings": False,
}
SIZE_KEYS = tuple(PARAMS) + tuple(FLOAT_PARAMS) + ("linear_attn_config", "gqa_interval", "gqa_layers") + tuple(CHECKED)
_NOT_SHAPE = ("seq_len", "batch_size", "n_seq")


def kda_sizes(sizes: dict) -> dict:
    """The delta rule's sizes under the trial's names."""
    return {name: int(sizes["linear_attn_config"][key]) for key, name in KDA_PARAMS.items()}


def gqa_layers(sizes: dict) -> list[bool]:
    """Whether each layer that is run is a grouped-query layer: one in every
    ``gqa_interval + 1``, from the first."""
    period = int(sizes["gqa_interval"]) + 1
    return [i % period == 0 for i in range(sizes["num_hidden_layers"])]


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  A program that has no
    such block would take ``block`` for a parameter it does not know and train
    GPT-2 blocks of these sizes: refuse it here, at once."""
    if importlib.util.find_spec("katib_tpu.models.kda_moe") is None:
        raise SystemExit(
            "families/kda_moe.py: this checkout's transformer_trial has no block 'kda_moe' "
            "(katib_tpu/models/kda_moe.py is missing): the configuration cannot run here"
        )
    wrong = {k: sizes[k] for k, want in CHECKED.items() if sizes[k] != want}
    run = sizes["num_hidden_layers"]
    listed = [i for i in sizes["gqa_layers"] if i < run]
    if wrong or listed != [i for i, gqa in enumerate(gqa_layers(sizes)) if gqa]:
        raise SystemExit(
            f"families/kda_moe.py: the block builds {CHECKED} and a grouped-query layer every "
            f"gqa_interval + 1 from the first; the file has {wrong}, gqa_layers {listed}"
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
    params += [pinned(name, value) for name, value in kda_sizes(sizes).items()]
    params.append(pinned("gqa_period", int(sizes["gqa_interval"]) + 1))
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
# operations and bytes, from shapes
# ---------------------------------------------------------------------------


def expert_params(sizes: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_product_cost(sizes: dict, assignments_held: float) -> dict:
    """Operations and HBM bytes of one step's grouped products over the
    experts held, forward + backward, from the COUNTED assignments to them
    (every layer has experts): as ``families/mla_moe.py`` counts them."""
    d, w = sizes["hidden_size"], sizes["moe_intermediate_size"]
    weights = sizes["num_hidden_layers"] * sizes["n_routed_experts"] * expert_params(sizes)
    rows_forward = (d * 2 + 2 * w * 4) + (w * 2 + d * 4)
    return {
        "flops": 6.0 * expert_params(sizes) * assignments_held,
        "bytes": float(weights * (2 + 2 + 4) + 3 * rows_forward * assignments_held),
    }


def matmul_params(sizes: dict) -> float:
    """Parameters that take part in a product for one token.  A delta-rule
    layer: q, k, v and the output at its heads, the two low-rank gates (rank
    ``head_dim``), the step's projection; a grouped-query layer: q, the gate
    and the output at the query heads, k and v at the key-value heads; every
    layer the router, the shared expert and the routed experts a token EXPECTS
    here (``num_experts_per_tok`` times the share held: 8 x 8/320 = 0.2); the
    head.  The convolutions' four taps a channel are counted in
    ``kda_layer_flops``."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    kz = kda_sizes(sizes)
    width, rank = kz["kda_heads"] * kz["kda_head_dim"], kz["kda_head_dim"]
    kda = 4 * d * width + 2 * (d * rank + rank * width) + d * kz["kda_heads"]
    gqa = 3 * d * nh * hd + 2 * d * nkv * hd
    routed = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / sizes["router_width"]
    experts = d * sizes["router_width"] + (sizes["n_shared_experts"] + routed) * expert_params(sizes)
    layers = sum(gqa if is_gqa else kda for is_gqa in gqa_layers(sizes))
    return layers + sizes["num_hidden_layers"] * experts + d * sizes["vocab_size"]


def kda_scan_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's delta rule, forward + backward,
    as the chunk algorithm at ``CHUNK`` (C) positions does it, counted here
    once so that a later kernel is read against the same work.  A chunk of a
    head, widths ``D`` (keys) and ``Dv``: the two intra-chunk products (keys
    against keys, queries against keys) 2 x 2C^2 D; ``(I + L)^-1`` by ten C x
    C products, 20 C^3; ``T (beta V)`` and ``T (beta K)`` 2C^2 (Dv + D); the
    start state into ``Delta`` and into the output 4 C D Dv; ``P Delta`` 2C^2
    Dv; the state's update 2 C D Dv.  The backward twice the forward.  Bytes:
    the forward reads q, k, v (bfloat16), g (float32) and beta and writes o
    (float32); the backward reads them and dO, and writes their gradients."""
    b, s = sizes["batch_size"], sizes["seq_len"]
    kz = kda_sizes(sizes)
    h, d = kz["kda_heads"], kz["kda_head_dim"]
    dv, c = d, CHUNK
    chunk = 4 * c * c * d + 20 * c**3 + 2 * c * c * (dv + d) + 6 * c * d * dv + 2 * c * c * dv
    chunks = b * h * -(-s // c)
    tokens = b * h * s
    operands = tokens * (2 * d * 2 + dv * 2 + d * 4 + 4)  # q, k, v, g, beta
    return {
        "flops": 3.0 * chunk * chunks,
        "bytes": float(operands + tokens * dv * 4 + (operands + tokens * dv * 4) + operands),
        "calls_per_step": sum(not is_gqa for is_gqa in gqa_layers(sizes)),
    }


def kda_layer_flops(sizes: dict) -> float:
    """What a delta-rule layer computes beyond its products, forward and
    backward: the scan (``kda_scan_cost``) and the convolutions (four taps a
    channel of q, k and v, 2 operations each)."""
    kz = kda_sizes(sizes)
    tokens = sizes["batch_size"] * sizes["seq_len"]
    conv = 3.0 * 2 * kz["conv_size"] * 3 * kz["kda_heads"] * kz["kda_head_dim"] * tokens
    return kda_scan_cost(sizes)["flops"] + conv


def attention_flops_fwd(sizes: dict) -> float:
    """QK^T and PV over the causal pairs of one grouped-query layer."""
    s = sizes["seq_len"]
    return 4.0 * sizes["batch_size"] * sizes["num_attention_heads"] * s * (s + 1) / 2 * sizes["head_dim"]


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per product
    parameter and token, causal attention on the grouped-query layers
    (backward twice the forward), the delta rule's chunk algorithm and
    convolutions on the others.  Rematerialised blocks are not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    mixers = sum(3.0 * attention_flops_fwd(sizes) if is_gqa else kda_layer_flops(sizes) for is_gqa in gqa_layers(sizes))
    return 6.0 * matmul_params(sizes) * tokens + mixers


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one grouped-query layer's attention,
    forward + backward, as ``families/gqa_moe.py`` counts a full layer (the
    delta-rule layers run no attention kernel)."""
    b, s, hd = sizes["batch_size"], sizes["seq_len"], sizes["head_dim"]
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    row_q, row_kv = b * nh * s * hd * 2, b * nkv * s * hd * 2
    return {
        "flops": 3.0 * attention_flops_fwd(sizes),
        "bytes": float((2 * row_q + 2 * row_kv) + (4 * row_q + 4 * row_kv) + 2 * b * nh * s * 4),
        "calls_per_step": sum(gqa_layers(sizes)),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_SHAPE_KEYS = [k for k in tuple(PARAMS) + tuple(FLOAT_PARAMS) if k not in _NOT_SHAPE]


def shape_of(sizes: dict) -> tuple:
    """What shapes the weights and the reference's programs, hashable."""
    return tuple(sizes[k] for k in _SHAPE_KEYS) + tuple(kda_sizes(sizes).values()) + (tuple(gqa_layers(sizes)),)


def _named(shape: tuple) -> dict:
    return dict(zip(_SHAPE_KEYS + list(KDA_PARAMS.values()) + ["kinds"], shape, strict=True))


def a_log_init(key, shape, dtype=None):
    """Kimi Linear's ``A_log``: the log of a draw from U(1, 16), a head (as
    ``models/kda_moe.py`` draws it)."""
    import jax
    import jax.numpy as jnp

    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=None):
    """Kimi Linear's ``dt_bias``: the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], floored at 1e-4, a channel."""
    import jax
    import jax.numpy as jnp

    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(sizes: dict):
    return _init_program(shape_of(sizes))()


@functools.lru_cache(maxsize=None)
def _init_program(shape: tuple):
    """Initial weights as flax draws them from ``PRNGKey(0)`` for modules of
    the program's names, shapes, initialisers and order of declaration (the
    trial's seed never reaches its weights).  The skeleton below only declares
    the parameters; the reference's arithmetic is ``_layer_functions``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    d, hd = z["hidden_size"], z["head_dim"]
    nh, nkv = z["num_attention_heads"], z["num_key_value_heads"]
    kh, kd = z["kda_heads"], z["kda_head_dim"]
    width, held = z["moe_intermediate_size"], z["n_routed_experts"]
    dense = functools.partial(nn.Dense, use_bias=False)

    class Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    class Conv(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("kernel", nn.initializers.lecun_normal(), (z["conv_size"], x.shape[-1]))
            return x

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense(nh * hd, name="q_proj")(x)
            dense(nkv * hd, name="k_proj")(x)
            dense(nkv * hd, name="v_proj")(x)
            dense(nh * hd, name="gate_proj")(x)
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (nh * hd,)))

    class Kda(nn.Module):
        @nn.compact
        def __call__(self, x):
            for n in ("q", "k", "v"):
                Conv(name=f"{n}_conv")(dense(kh * kd, name=f"{n}_proj")(x))
            dense(kh * kd, name="f_b_proj")(dense(kd, name="f_a_proj")(x))
            self.param("A_log", a_log_init, (kh,))
            self.param("dt_bias", dt_bias_init, (kh * kd,))
            dense(kh, name="b_proj")(x)
            Norm(name="o_norm")(jnp.zeros((kd,)))
            dense(kh * kd, name="g_b_proj")(dense(kd, name="g_a_proj")(x))
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (kh * kd,)))

    class Shared(nn.Module):
        @nn.compact
        def __call__(self, x):
            shared = width * z["n_shared_experts"]
            dense(shared, name="gate_proj")(x)
            return dense(d, name="down_proj")(dense(shared, name="up_proj")(x))

    class Experts(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("router", nn.initializers.lecun_normal(), (d, z["router_width"]))
            stacked = nn.initializers.variance_scaling(1.0, "fan_in", "truncated_normal", batch_axis=(0,))
            self.param("experts_gate", stacked, (held, d, width))
            self.param("experts_up", stacked, (held, d, width))
            self.param("experts_down", stacked, (held, width, d))
            if z["n_shared_experts"]:
                Shared(name="shared")(x)
            return x

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, gqa):
            Norm(name="input_norm")(x)
            (Attention(name="attn") if gqa else Kda(name="kda"))(x)
            Norm(name="post_attn_norm")(x)
            return Experts(name="moe")(x)

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(z["vocab_size"], d, name="embed")(tokens)
            for i, gqa in enumerate(z["kinds"]):
                x = Layer(name=f"layer_{i}")(x, gqa)
            Norm(name="norm")(x)
            return dense(z["vocab_size"], name="head")(x)

    @jax.jit
    def make():
        return from_program_tree(LM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    return make


def from_program_tree(tree: dict) -> dict:
    """The reference's weights from a parameter tree of the program's names
    (``KdaMoeLM``'s ``params``, or the skeleton's): the grouped-query layers'
    weights stacked in order (``gqa``), and the delta-rule layers' (``kda``),
    each layer a flat dict (the two kinds have different weights)."""
    import jax
    import jax.numpy as jnp

    kinds = {"gqa": [], "kda": []}
    i = 0
    while f"layer_{i}" in tree:
        layer = tree[f"layer_{i}"]
        moe = layer["moe"]
        w = {
            "norm1": layer["input_norm"]["scale"],
            "norm2": layer["post_attn_norm"]["scale"],
            **{k: moe[k] for k in ("router", "experts_gate", "experts_up", "experts_down")},
        }
        if "shared" in moe:
            w.update({f"shared_{n}": moe["shared"][f"{n}_proj"]["kernel"] for n in ("gate", "up", "down")})
        if "attn" in layer:
            w.update({n: layer["attn"][f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "gate", "o")})
            kinds["gqa"].append(w)
        else:
            kda = layer["kda"]
            w.update({n: kda[f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "f_a", "f_b", "b", "g_a", "g_b", "o")})
            w.update({f"{n}_conv": kda[f"{n}_conv"]["kernel"] for n in ("q", "k", "v")})
            w.update(A_log=kda["A_log"], dt_bias=kda["dt_bias"], o_norm=kda["o_norm"]["scale"])
            kinds["kda"].append(w)
        i += 1
    return {
        "embed": tree["embed"]["embedding"],
        "norm": tree["norm"]["scale"],
        "head": tree["head"]["kernel"],
        **{kind: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ws) for kind, ws in kinds.items() if ws},
    }


def _divisor_at_most(n: int, limit: float) -> int:
    r = max(1, min(n, int(limit)))
    while n % r:
        r -= 1
    return r


#: the bytes of one query block's attention scores, float32 at every head
SCORE_BYTES = 0.27e9
#: positions of the recurrence between two kept states (its backward keeps
#: one state a block and computes a block's again)
TOKEN_BLOCK = 64
#: the reference's programs compiled with the least effort on their speed:
#: the train step's deviceless v5e compile takes 20 s, not 143, and the eval's
#: 4 s, not 72 (with the default effort a run of the cell whose programs and
#: reference were all cold read 368 s on a TPU v5e, over a 360 s run limit)
COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}
#: the faults ``_layer_functions`` knows (``half_batch`` and
#: ``state_unchanged`` are the train step's)
LAYER_FAULTS = ("beta_one", "decay_per_head", "no_gate", "state_bf16")


def _layer_functions(shape: tuple, precision: str, fault: str | None, r: int, s: int) -> dict:
    """The layer for ``r`` rows of ``s`` positions as plain functions of the
    residual stream ``x`` [R, S, D] and one layer's weights ``w``:
    ``attention(x, w)`` and ``kda(x, w)`` (x + the mixer of the normed x),
    ``moe(x, w)`` (x + the held and shared experts' part of the normed x);
    and ``rms_norm``, ``delta_rule``, ``mm``."""
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    mm = _matmul(precision)
    hd, nh, nkv = z["head_dim"], z["num_attention_heads"], z["num_key_value_heads"]
    kh, kd = z["kda_heads"], z["kda_head_dim"]
    group = nh // nkv
    first, held, top = z["experts_held_first"], z["n_routed_experts"], z["num_experts_per_tok"]
    eps = z["rms_norm_eps"]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    def silu(x):
        return x * jax.nn.sigmoid(x)

    def gated(o, gate):
        return o if fault == "no_gate" else o * jax.nn.sigmoid(gate)

    # -- grouped-query attention without positions, queries in blocks
    q_block = _divisor_at_most(s, SCORE_BYTES // (r * nh * s * 4))
    key_pos = jnp.arange(s)

    def attention(x, w):
        h = rms_norm(x, w["norm1"])
        q = mm("rsd,de->rse", h, w["q"]).reshape(r, s, nkv, group, hd)
        k = mm("rsd,de->rse", h, w["k"]).reshape(r, s, nkv, hd)
        v = mm("rsd,de->rse", h, w["v"]).reshape(r, s, nkv, hd)

        @jax.checkpoint
        def attend(block):
            q_blk, t0 = block  # [R, q_block, Hkv, group, hd]
            seen = key_pos[None, :] <= t0 + jnp.arange(q_block)[:, None]
            scores = mm("rqngd,rknd->rngqk", q_blk, k) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm("rngqk,rknd->rqngd", probs, v)

        blocks = jnp.moveaxis(q.reshape(r, s // q_block, q_block, nkv, group, hd), 1, 0)
        o = jax.lax.map(attend, (blocks, jnp.arange(0, s, q_block)))
        o = jnp.moveaxis(o, 0, 1).reshape(r, s, nh * hd)
        return x + mm("rse,ed->rsd", gated(o, mm("rsd,de->rse", h, w["gate"])), w["o"])

    # -- the delta rule, token by token
    def delta_rule(q, k, v, g, beta):
        """``q``, ``k``, ``v``, ``g`` [R, S, H, D], ``beta`` [R, S, H] ->
        ``o`` [R, S, H, D]; the state [R, H, D, Dv] starts at 0."""

        def token(state, xs):
            qt, kt, vt, gt, bt = xs
            decayed = jnp.exp(gt)[..., None] * state
            pred = mm("rhd,rhde->rhe", kt, decayed)
            state = decayed + bt[..., None, None] * kt[..., None] * (vt - pred)[..., None, :]
            if fault == "state_bf16":
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, mm("rhd,rhde->rhe", qt, state)

        @jax.checkpoint
        def block(state, xs):
            return jax.lax.scan(token, state, xs)

        n = _divisor_at_most(s, TOKEN_BLOCK)
        xs = tuple(jnp.moveaxis(a, 1, 0).reshape(s // n, n, *a.shape[:1], *a.shape[2:]) for a in (q, k, v, g, beta))
        _, o = jax.lax.scan(block, jnp.zeros((r, kh, kd, kd), jnp.float32), xs)
        return jnp.moveaxis(o.reshape(s, r, kh, kd), 0, 1)

    def conv(y, taps):  # [R, S, C], [size, C]: causal, depthwise, no bias
        size = taps.shape[0]
        padded = jnp.pad(y, ((0, 0), (size - 1, 0), (0, 0)))
        return sum(taps[i] * padded[:, i : i + s] for i in range(size))

    def l2_normalized(x):
        return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

    def kda(x, w):
        h = rms_norm(x, w["norm1"])

        def branch(n):
            return silu(conv(mm("rsd,de->rse", h, w[n]), w[f"{n}_conv"])).reshape(r, s, kh, kd)

        q = l2_normalized(branch("q")) / math.sqrt(kd)
        k = l2_normalized(branch("k"))
        v = branch("v")
        f = mm("rse,ef->rsf", mm("rsd,de->rse", h, w["f_a"]), w["f_b"]) + w["dt_bias"]
        g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(f.reshape(r, s, kh, kd))
        if fault == "decay_per_head":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        beta = jax.nn.sigmoid(mm("rsd,dh->rsh", h, w["b"]))
        if fault != "beta_one":
            beta = 2.0 * beta
        o = rms_norm_heads(delta_rule(q, k, v, g, beta), w["o_norm"])
        gate = mm("rse,ef->rsf", mm("rsd,de->rse", h, w["g_a"]), w["g_b"]).reshape(r, s, kh, kd)
        return x + mm("rse,ed->rsd", gated(o, gate).reshape(r, s, kh * kd), w["o"])

    def rms_norm_heads(o, g):  # over each head's channels, one weight for all heads
        return o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps) * g

    # -- the experts: the router over all of them, the held ones over every
    # token (0 where not routed), the shared one
    def moe(x, w):
        h = rms_norm(x, w["norm2"])
        scores = jax.nn.sigmoid(mm("rsd,de->rse", h, w["router"]))
        chosen_scores, chosen = jax.lax.top_k(scores, top)
        weights = chosen_scores / (jnp.sum(chosen_scores, axis=-1, keepdims=True) + 1e-20)
        per_expert = jnp.sum(jax.nn.one_hot(chosen - first, held) * weights[..., None], axis=-2)  # [R, S, held]

        @jax.checkpoint
        def add_expert(acc, e):
            gate, up, down, weight = e
            hidden = silu(mm("rsd,de->rse", h, gate)) * mm("rsd,de->rse", h, up)
            return acc + weight[..., None] * mm("rse,ed->rsd", hidden, down), None

        routed, _ = jax.lax.scan(
            add_expert,
            jnp.zeros_like(x),
            (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(per_expert, -1, 0)),
        )
        if "shared_gate" in w:
            hidden = silu(mm("rsd,de->rse", h, w["shared_gate"])) * mm("rsd,de->rse", h, w["shared_up"])
            routed = routed + mm("rse,ed->rsd", hidden, w["shared_down"])
        return x + routed

    return {"rms_norm": rms_norm, "attention": attention, "kda": kda, "moe": moe, "delta_rule": delta_rule, "mm": mm}


def _forward(params, tokens, shape: tuple, precision: str, fault: str | None):
    """Mean next-token cross entropy of ``tokens`` [R, S]: every layer
    rematerialised in its backward, the head and the loss in blocks of
    positions (a block's float32 logits near 0.3 GB)."""
    import jax
    import jax.numpy as jnp

    r, s = tokens.shape
    f = _layer_functions(shape, precision, fault, r, s)
    x = params["embed"][tokens]
    # the layers in order, each rematerialised in its backward; a run of
    # delta-rule layers is a ``lax.scan`` over their stacked weights (one
    # body to compile, not one a layer)
    done = {"gqa": 0, "kda": 0}
    for gqa, run in itertools.groupby(_named(shape)["kinds"]):
        kind, n = ("gqa" if gqa else "kda"), len(list(run))
        mixer = f["attention"] if gqa else f["kda"]
        layer = jax.checkpoint(lambda x, w, mixer=mixer: (f["moe"](mixer(x, w), w), None))
        ws = jax.tree_util.tree_map(lambda a, lo=done[kind]: a[lo : lo + n], params[kind])
        x, _ = jax.lax.scan(layer, x, ws)
        done[kind] += n
    x = f["rms_norm"](x, params["norm"])
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
def _programs(shape: tuple, precision: str, fault: str | None):
    """The reference's two jitted programs: one train step (loss and gradient,
    then clip and AdamW) and the eval loss; ``lr`` is an operand, so one
    compile serves every rate.  ``fault`` plants one of the faults the
    correctness tests must see (never set by a benchmark run)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, compiler_options=COMPILER_OPTIONS)
    def eval_loss(params, tokens):
        return _forward(params, tokens, shape, precision, fault)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2), compiler_options=COMPILER_OPTIONS)
    def step(params, m, v, count, lr, tokens):
        if fault == "half_batch":
            # half the rows; of a single row, the first half of its positions
            rows, s = tokens.shape
            tokens = tokens[: rows // 2] if rows > 1 else tokens[:, : s // 2]
        loss, grads = jax.value_and_grad(_forward)(params, tokens, shape, precision, fault)
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
        return new_params, pick(1), pick(2), loss

    return step, eval_loss


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
    step, eval_loss = _programs(shape_of(sizes), precision, fault)
    params = init_params(sizes)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    eval_tokens = jnp.asarray(eval_rows)
    out = {name: {} for name in METRICS}
    for s in range(last + 1):
        params, m, v, loss = step(params, m, v, jnp.int32(s), jnp.float32(lr_at(s, lr, steps)), jnp.asarray(rows[s]))
        if s in COMPARE_STEPS:
            out["loss"][s] = float(loss)
            out["eval_loss"][s] = float(eval_loss(params, eval_tokens))
    del params, m, v
    return out
