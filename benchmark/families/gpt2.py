"""Family ``gpt2``: GPT-2 blocks trained by ``transformer_trial``.

Everything that belongs to the family of configurations and not to one cell:

- ``experiment_doc``: the experiment document (what ``katib-tpu run`` would be
  given as YAML) from a configuration's sizes and a traffic mix;
- the plain reference of a trial's first reports (``reference_series``): data,
  initial weights, forward, loss, backward, clip, AdamW, written from GPT-2's
  equations with the departures the configuration files list.  It imports
  nothing of ``katib_tpu``;
- the operations and bytes of a step and of the attention kernel, from shapes;
- ``compare``: which numbers of a trial's series are held against the
  reference.

Departures of the program's block from the published GPT-2, reproduced here:
no bias on the QKV and output projections, an untied output head with a bias,
LayerNorm eps 1e-6, tanh GELU, learned positions.  The program computes in
bfloat16 with float32 parameters; the reference computes in float32 with
``highest`` matmul precision.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: reports of a trial that the reference follows (``train_lm`` reports every
#: 10 steps and on the last one; two reports are 11 optimizer steps)
COMPARE_STEPS = (0, 10)
METRICS = ("loss", "eval_loss")
TRAIN_FN = "katib_tpu.models.transformer.transformer_trial"
#: spans the program writes per trial, and the jitted programs of a trial
STEP_MODULE = "jit_step_fn"
EVAL_MODULE = "jit_eval_fn"
#: the Pallas kernels of ops/flash_attention.py (forward, dq, dkv) are the
#: step program's only custom calls of this target; the device trace names an
#: operation by its HLO text
FLASH_KERNEL_MARK = 'custom_call_target="tpu_custom_call"'

SIZE_KEYS = ("d_model", "n_heads", "n_layers", "seq_len", "vocab_size", "batch_size", "n_seq")


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  ``lr_values`` narrows the
    discrete ``lr`` list (warm-up trials pin one value)."""

    def pinned(pname: str, value: int) -> dict:
        return {
            "name": pname,
            "parameterType": "int",
            "feasibleSpace": {"min": str(value), "max": str(value)},
        }

    params = []
    for p in traffic["parameters"]:
        p = dict(p)
        if p["name"] == "lr" and lr_values is not None:
            p["feasibleSpace"] = {"list": [str(v) for v in lr_values]}
        params.append(p)
    params += [pinned(k, int(sizes[k])) for k in SIZE_KEYS]
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


def lr_values(traffic: dict) -> list[float]:
    (p,) = [p for p in traffic["parameters"] if p["name"] == "lr"]
    return [float(v) for v in p["feasibleSpace"]["list"]]


# ---------------------------------------------------------------------------
# operations and bytes, from shapes
# ---------------------------------------------------------------------------


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    QKV (3d^2), output projection (d^2), MLP (8d^2) per layer, and the
    vocabulary head (d*V).  Embedding look-ups are gathers, not counted."""
    d = sizes["d_model"]
    return sizes["n_layers"] * 12 * d * d + d * sizes["vocab_size"]


def attention_flops_fwd(sizes: dict) -> float:
    """QK^T and PV over the causal half: 2 matmuls x 2*S*S*Dh / 2."""
    b, h, s = sizes["batch_size"], sizes["n_heads"], sizes["seq_len"]
    dh = sizes["d_model"] // h
    return 2.0 * b * h * s * s * dh


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per matmul
    parameter and token, plus causal attention (backward twice the forward).
    What the kernel recomputes in its backward pass is not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    return 6.0 * matmul_params(sizes) * tokens + 3.0 * attention_flops_fwd(sizes)


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one layer's attention, forward + backward,
    as the algorithm needs them: forward reads q, k, v and writes o; backward
    reads q, k, v, o, do and writes dq, dk, dv (bf16), plus the float32
    log-sum-exp written once and read once.  Operations: 2 causal matmuls
    forward, 4 backward; the kernel's recomputation of the scores is not
    counted."""
    b, h, s = sizes["batch_size"], sizes["n_heads"], sizes["seq_len"]
    dh = sizes["d_model"] // h
    tensor = b * h * s * dh * 2
    lse = b * h * s * 4
    return {
        "flops": 3.0 * attention_flops_fwd(sizes),
        "bytes": float(4 * tensor + 8 * tensor + 2 * lse),
        "calls_per_step": sizes["n_layers"],
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def markov_tokens(vocab_size: int, n_seq: int, seq_len: int, seed: int, branching: int = 4):
    """The trial's synthetic data set: a sparse first-order Markov chain from
    the seed (numpy's default generator, the draws in the program's order)."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab_size, size=(vocab_size, branching))
    out = np.empty((n_seq, seq_len), np.int32)
    state = rng.integers(0, vocab_size, size=n_seq)
    for t in range(seq_len):
        out[:, t] = state
        state = succ[state, rng.integers(0, branching, size=n_seq)]
    return out


def batches(data: np.ndarray, batch_size: int, n_steps: int):
    """Train batches of the first ``n_steps`` steps and the eval batch, as the
    trial draws them: a held-out tail of a tenth, rows drawn with replacement
    by a generator seeded 0."""
    n_eval = max(batch_size, len(data) // 10)
    train, heldout = data[:-n_eval], data[-n_eval:]
    rng = np.random.default_rng(0)
    rows = [train[rng.integers(0, len(train), size=batch_size)] for _ in range(n_steps)]
    return rows, heldout[:batch_size]


def lr_at(count: int, peak: float, steps: int, warmup_frac: float = 0.1) -> float:
    """Linear warm-up from 0 over max(1, int(steps*frac)) updates, then a
    cosine to 0 at ``steps``; ``count`` is the number of updates made."""
    warm = max(1, int(steps * warmup_frac))
    if count < warm:
        return peak * count / warm
    span = steps - warm
    frac = min(count - warm, span) / span
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def init_params(sizes: dict):
    return _init_program(sizes["d_model"], sizes["n_layers"], sizes["vocab_size"], sizes["seq_len"])()


@functools.lru_cache(maxsize=None)
def _init_program(d: int, n_layers: int, vocab_size: int, seq_len: int):
    """Initial weights as flax draws them from ``PRNGKey(0)`` for modules of
    these names and shapes (the trial's seed never reaches its weights).  The
    skeleton below only declares the parameters; the reference's arithmetic is
    ``_forward``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            nn.LayerNorm()(x)
            nn.Dense(3 * d, use_bias=False)(x)
            nn.Dense(d, use_bias=False)(x)
            nn.LayerNorm()(x)
            h = nn.Dense(4 * d)(x)
            nn.Dense(d)(h)
            return x

    class TransformerLM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(vocab_size, d)(tokens)
            nn.Embed(seq_len, d)(tokens)
            for _ in range(n_layers):
                x = Block()(x)
            nn.LayerNorm()(x)
            return nn.Dense(vocab_size)(x)

    @jax.jit
    def make():
        tree = TransformerLM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        blocks = [tree[f"Block_{i}"] for i in range(n_layers)]

        def stack(*path):
            leaves = []
            for blk in blocks:
                node = blk
                for k in path:
                    node = node[k]
                leaves.append(node)
            return jnp.stack(leaves)

        return {
            "wte": tree["Embed_0"]["embedding"],
            "wpe": tree["Embed_1"]["embedding"],
            "ln_f_g": tree["LayerNorm_0"]["scale"],
            "ln_f_b": tree["LayerNorm_0"]["bias"],
            "head_w": tree["Dense_0"]["kernel"],
            "head_b": tree["Dense_0"]["bias"],
            "layers": {
                "ln1_g": stack("LayerNorm_0", "scale"),
                "ln1_b": stack("LayerNorm_0", "bias"),
                "qkv_w": stack("Dense_0", "kernel"),
                "out_w": stack("Dense_1", "kernel"),
                "ln2_g": stack("LayerNorm_1", "scale"),
                "ln2_b": stack("LayerNorm_1", "bias"),
                "fc_w": stack("Dense_2", "kernel"),
                "fc_b": stack("Dense_2", "bias"),
                "proj_w": stack("Dense_3", "kernel"),
                "proj_b": stack("Dense_3", "bias"),
            },
        }

    return make


def _matmul(precision: str):
    """The one place the reference's precision lives.  ``f32``: float32 at
    ``highest``.  The controls: ``bf16`` and ``fp8`` round both operands
    (fp8: e4m3 with a per-tensor scale to its largest value) and accumulate in
    float32."""
    import jax
    import jax.numpy as jnp

    def quantised(x):
        if precision == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(jax.lax.stop_gradient(x))), 1e-30)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale

    def mm(spec, a, b):
        if precision != "f32":
            a, b = quantised(a), quantised(b)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    return mm


def _forward(params, tokens, n_heads: int, precision: str):
    """Mean next-token cross entropy of ``tokens`` [R, S]."""
    import jax
    import jax.numpy as jnp

    mm = _matmul(precision)
    s = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][jnp.arange(s)][None]

    def layer_norm(x, g, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-6) * g + b

    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def block(x, w):
        r, _, d = x.shape
        dh = d // n_heads
        h = layer_norm(x, w["ln1_g"], w["ln1_b"])
        qkv = mm("rsd,de->rse", h, w["qkv_w"])
        q, k, v = (t.reshape(r, s, n_heads, dh) for t in jnp.split(qkv, 3, axis=-1))
        scores = mm("rqhd,rkhd->rhqk", q, k) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = mm("rhqk,rkhd->rqhd", probs, v).reshape(r, s, d)
        x = x + mm("rsd,de->rse", o, w["out_w"])
        h = layer_norm(x, w["ln2_g"], w["ln2_b"])
        h = jax.nn.gelu(mm("rsd,de->rse", h, w["fc_w"]) + w["fc_b"], approximate=True)
        return x + mm("rse,ed->rsd", h, w["proj_w"]) + w["proj_b"], None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = layer_norm(x, params["ln_f_g"], params["ln_f_b"])
    logits = mm("rsd,dv->rsv", x, params["head_w"]) + params["head_b"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


@functools.lru_cache(maxsize=None)
def _programs(n_heads: int, rows_per_block: int, precision: str, fault: str | None):
    """The reference's two jitted programs: one train step (loss and gradient
    in blocks of rows so that it fits beside nothing else on the chip, then
    clip and AdamW) and the eval loss.  ``fault`` plants one of the faults the
    correctness tests must see (never set by a benchmark run)."""
    import jax
    import jax.numpy as jnp

    def blocks_of(tokens):
        r = math.gcd(rows_per_block, tokens.shape[0])
        return tokens.reshape(-1, r, tokens.shape[1])

    def mean_loss(params, tokens):
        return jnp.mean(jax.lax.map(lambda t: _forward(params, t, n_heads, precision), blocks_of(tokens)))

    @jax.jit
    def eval_loss(params, tokens):
        return mean_loss(params, tokens)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, lr, tokens):
        if fault == "half_batch":
            tokens = tokens[: tokens.shape[0] // 2]
        blocks = blocks_of(tokens)

        def one(acc, t):
            loss, g = jax.value_and_grad(_forward)(params, t, n_heads, precision)
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
    """Rows the reference differentiates at once: attention scores in float32
    are R*H*S*S*4 bytes, several times over; keep one block near 0.5 GB."""
    per_row = sizes["n_heads"] * sizes["seq_len"] ** 2 * 4 + sizes["seq_len"] * sizes["vocab_size"] * 4
    r = max(1, int(0.6e9 // per_row))
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
    step, eval_loss = _programs(sizes["n_heads"], rows_per_block(sizes), precision, fault)
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


# ---------------------------------------------------------------------------
# what is compared
# ---------------------------------------------------------------------------


def compare(series: dict, reference: dict) -> dict:
    """Gaps between a trial's reported series and the reference's, relative to
    the reference: ``first_loss_gap`` over the reports of step 0 (forward and
    loss; the schedule's first update has rate 0), ``trained_loss_gap`` over
    the reports of step 10 (ten updates: backward, clip, AdamW)."""
    gaps = {}
    for name, step in (("first_loss_gap", COMPARE_STEPS[0]), ("trained_loss_gap", COMPARE_STEPS[1])):
        worst = 0.0
        for metric in METRICS:
            got = series.get(metric, {}).get(step)
            want = reference[metric][step]
            if got is None or not math.isfinite(got):
                worst = math.inf
            else:
                worst = max(worst, abs(got - want) / abs(want))
        gaps[name] = worst
    return gaps
