"""Family ``looped``: a stack of layers run several times over the same
weights, an exit after every pass, a learned exit gate and the expected-exit
loss, as ``Ouro-2.6B`` configures them, trained by ``transformer_trial`` with
``block: looped``.

What the family adds to the benchmark, beside what the three older families
have:

- a configuration file whose top-level keys are the published ``config.json``'s
  own (``SIZE_KEYS``): ``experiment_doc`` turns them into the trial's
  parameters (``PARAMS``, ``FLOAT_PARAMS``).  ``exit_beta`` is no key of the
  published file: the configuration pins it (``assumed``);
- the plain reference (``_losses``), from the equations, with ``D`` the hidden
  size, ``T`` = ``total_ut_steps`` and ``L`` = ``num_hidden_layers``:
  block ``l``: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``, four
  RMSNorms with weights only, no bias anywhere; ``Attn``: heads of
  ``head_dim``, rotary over the whole head with the halves paired, causal
  softmax in blocks of queries against all keys; ``MLP``:
  ``(silu(h W_gate) * (h W_up)) W_down``;
  pass ``t``: ``z_0 = Embed(tokens)``, ``z_t = N_f(B_L(...B_1(z_{t-1})))``: a
  ``lax.scan`` over the passes around a ``lax.scan`` over the layers' stacked
  weights, as ``families/gpt2.py`` scans its layers (the weights are closed
  over by the outer loop, so autodiff sums a weight's gradient over the
  passes).  Written as Python loops the reference's train step compiled to
  an executable of 227 MB (my chip run, PR 37), more than the chip machine's
  compile cache holds at all: it was compiled again in every run;
  exit ``t``: ``l_t[i] = CE(z_t[i] W_head, tokens[i+1])``, the head in blocks
  of positions;
  gate: ``lambda_t[i] = sigmoid(z_t[i] . w_g + b_g)``;
  ``q_t = lambda_t prod_{j<t}(1 - lambda_j)``, ``q_T = prod_{j<T}(1 -
  lambda_j)``;
  the trial's ``loss``: ``mean_i [sum_t q_t[i] l_t[i] - beta H(q[i])]``; its
  ``eval_loss``: ``mean_i l_T[i]``.  It imports nothing of ``katib_tpu``;
- faults ``half_batch``, ``state_unchanged`` and, new here, ``one_pass`` (the
  stack runs once: ``T`` = 1) and ``even_exits`` (the gate left out of the
  loss: every exit weighs ``1/T``);
- ``step_flops`` and ``flash_attention_cost`` count the ``T`` passes of every
  layer and the ``T`` heads;
- ``step_parts``: a step's device time in the exits' heads and cross entropies
  and outside them and the optimizer's update, for the readers
  ``layer_metrics/exit_loss_ms.py`` and ``loop_pass_ms.py``.

Departures from the published model, reproduced here: no early exit at
evaluation (the published threshold is 1: nothing exits early); flax's default
initialisers from ``PRNGKey(0)``.  The program computes in bfloat16 with
float32 parameters, gate, logits and losses; the reference computes in float32
with ``highest`` matmul precision.
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

BLOCK = "looped"
#: a ``tpu_custom_call`` names its operands' layouts, first operand first: the
#: attention kernels take q (bfloat16) first (families/mla_moe.py has the mark)
FLASH_KERNEL_MARK = "operand_layout_constraints={bf16["
#: the device trace names an operation by its HLO text, operands with their
#: names: the optimizer's update is where a step first reads AdamW's moments
OPTIMIZER_MARK = "opt_state"

#: the trial's integer parameters, by the configuration file's key
PARAMS = {
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "head_dim": "head_dim",
    "intermediate_size": "mlp_width",
    "num_hidden_layers": "n_layers",
    "total_ut_steps": "ut_steps",
    "vocab_size": "vocab_size",
    "seq_len": "seq_len",
    "batch_size": "batch_size",
    "n_seq": "n_seq",
}
#: those that are not integers
FLOAT_PARAMS = {"rope_theta": "rope_theta", "rms_norm_eps": "eps", "exit_beta": "exit_beta"}
#: read and checked, and no parameter of the trial: the block has as many
#: key-value heads as query heads
CHECKED = ("num_key_value_heads",)
SIZE_KEYS = tuple(PARAMS) + tuple(FLOAT_PARAMS) + CHECKED
_NOT_SHAPE = ("seq_len", "batch_size", "n_seq")


# ---------------------------------------------------------------------------
# the experiment document
# ---------------------------------------------------------------------------


def experiment_doc(
    name: str, sizes: dict, traffic: dict, seed: int, *, lr_values=None, max_trials=None
) -> dict:
    """The experiment a user of this sweep submits.  A program that has no
    such block would take ``block`` for a parameter it does not know and train
    GPT-2 blocks of these sizes: refuse it here, at once."""
    if importlib.util.find_spec("katib_tpu.models.looped") is None:
        raise SystemExit(
            "families/looped.py: this checkout's transformer_trial has no block 'looped' "
            "(katib_tpu/models/looped.py is missing): the configuration cannot run here"
        )
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise SystemExit(
            "families/looped.py: block 'looped' has as many key-value heads as query heads; the "
            f"configuration asks for {sizes['num_key_value_heads']} under {sizes['num_attention_heads']}"
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
# operations and bytes, from shapes; a step's parts, from the device trace
# ---------------------------------------------------------------------------


def layer_params(sizes: dict) -> int:
    """One block's product parameters: q, k, v and the output projection at
    ``heads x head_dim``, and the gated MLP's three."""
    d = sizes["hidden_size"]
    attention = 4 * d * sizes["num_attention_heads"] * sizes["head_dim"]
    return attention + 3 * d * sizes["intermediate_size"]


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a product for one token, each as often as
    a step applies it: every layer in every pass, and after every pass the
    head and the gate's vector."""
    d, passes = sizes["hidden_size"], sizes["total_ut_steps"]
    return passes * (sizes["num_hidden_layers"] * layer_params(sizes) + d * sizes["vocab_size"] + d)


def attention_flops_fwd(sizes: dict) -> float:
    """QK^T and PV over the causal half of one application of one layer."""
    b, h, s = sizes["batch_size"], sizes["num_attention_heads"], sizes["seq_len"]
    return 2.0 * b * h * s * s * sizes["head_dim"]


def layer_applications(sizes: dict) -> int:
    """Times a step runs a block forward: every layer in every pass."""
    return sizes["total_ut_steps"] * sizes["num_hidden_layers"]


def step_flops(sizes: dict) -> float:
    """Operations one train step requires, forward and backward: 6 per product
    parameter and token as often as it is applied, plus causal attention in
    every application of a layer (backward twice the forward).  Rematerialised
    blocks and the kernel's recomputation are not counted."""
    tokens = sizes["batch_size"] * sizes["seq_len"]
    attention = 3.0 * attention_flops_fwd(sizes) * layer_applications(sizes)
    return 6.0 * matmul_params(sizes) * tokens + attention


def flash_attention_cost(sizes: dict) -> dict:
    """Operations and HBM bytes of one application of one layer's attention,
    forward + backward, as ``families/gpt2.py`` counts them; a step makes
    ``total_ut_steps x num_hidden_layers`` such calls."""
    b, h, s = sizes["batch_size"], sizes["num_attention_heads"], sizes["seq_len"]
    tensor = b * h * s * sizes["head_dim"] * 2
    lse = b * h * s * 4
    return {
        "flops": 3.0 * attention_flops_fwd(sizes),
        "bytes": float(4 * tensor + 8 * tensor + 2 * lse),
        "calls_per_step": layer_applications(sizes),
    }


def exit_loss_mark(sizes: dict) -> str:
    """What the HLO text of every operation of the exits' heads and cross
    entropies holds, and no other operation's: an array with the positions of
    a sequence beside the whole vocabulary (a chunk's logits, as a result or
    as an operand: the head's forward product, the log-sum-exp and the target's
    logit, the logits' gradient inside the two backward products)."""
    return f"{sizes['seq_len']},{sizes['vocab_size']}"


def step_parts(sl, sizes: dict) -> dict | None:
    """Device milliseconds of one train step, the mean over the step
    executions whole inside the traced slice ``sl`` (``trace_reduce.Slice``):
    ``optimizer_ms`` from the first fusion that reads one of AdamW's moments
    to the step's end (the clip needs every gradient, so no update runs
    before the backward pass is done); ``exit_loss_ms`` the operations before
    that whose text holds ``exit_loss_mark`` (loops and branches themselves
    left out: their bodies' operations are events of their own);
    ``loop_pass_ms`` what is left of the step, over the passes: one pass of
    the stack, forward and backward.  ``None`` where the slice holds no
    step."""
    steps = sl.module_events(STEP_MODULE)
    if not steps:
        return None
    mark = exit_loss_mark(sizes)
    ops = sorted(sl.ops(), key=lambda ev: ev[1])
    nests = (" while(", " conditional(", " call(")
    whole = exit_loss = optimizer = 0.0
    for _name, a, b in steps:
        inside = [ev for ev in ops if a <= ev[1] and ev[2] <= b]
        updates = [ev[1] for ev in inside if OPTIMIZER_MARK in ev[0] and " fusion(" in ev[0]]
        t_update = min(updates, default=b)
        whole += b - a
        optimizer += b - t_update
        exit_loss += sum(
            ev[2] - ev[1]
            for ev in inside
            if ev[1] < t_update and mark in ev[0] and not any(n in ev[0] for n in nests)
        )
    n = len(steps)
    rest = whole - exit_loss - optimizer
    return {
        "exit_loss_ms": 1000.0 * exit_loss / n,
        "optimizer_ms": 1000.0 * optimizer / n,
        "loop_pass_ms": 1000.0 * rest / n / sizes["total_ut_steps"],
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

_SHAPE_KEYS = [k for k in tuple(PARAMS) + tuple(FLOAT_PARAMS) if k not in _NOT_SHAPE]


def shape_of(sizes: dict) -> tuple:
    """What shapes the weights and the reference's programs, hashable."""
    return tuple(sizes[k] for k in _SHAPE_KEYS)


def _named(shape: tuple) -> dict:
    return dict(zip(_SHAPE_KEYS, shape, strict=True))


def init_params(sizes: dict):
    return _init_program(shape_of(sizes))()


@functools.lru_cache(maxsize=None)
def _init_program(shape: tuple):
    """Initial weights as flax draws them from ``PRNGKey(0)`` for modules of
    the program's names, shapes, initialisers and order of declaration (the
    trial's seed never reaches its weights).  The skeleton below only declares
    the parameters, once: the reference's arithmetic is ``_losses``."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    d, width = z["hidden_size"], z["intermediate_size"]
    inner = z["num_attention_heads"] * z["head_dim"]
    dense = functools.partial(nn.Dense, use_bias=False)

    class Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            self.param("scale", nn.initializers.ones, (x.shape[-1],))
            return x

    class Attention(nn.Module):
        @nn.compact
        def __call__(self, x):
            for name in ("q_proj", "k_proj", "v_proj"):
                dense(inner, name=name)(x)
            return dense(d, name="o_proj")(jnp.zeros(x.shape[:-1] + (inner,)))

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            dense(width, name="gate_proj")(x)
            dense(width, name="up_proj")(x)
            return dense(d, name="down_proj")(jnp.zeros(x.shape[:-1] + (width,)))

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            for name in ("input_norm", "attn_out_norm", "post_attn_norm", "mlp_out_norm"):
                Norm(name=name)(x)
            Attention(name="attn")(x)
            return MLP(name="mlp")(x)

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(z["num_hidden_layers"]):
                Layer(name=f"layer_{i}")(x)
            return Norm(name="norm")(x)

    class LM(nn.Module):
        @nn.compact
        def __call__(self, tokens):
            x = nn.Embed(z["vocab_size"], d, name="embed")(tokens)
            Stack(name="stack")(x)
            nn.Dense(1, name="exit_gate")(x)
            return dense(z["vocab_size"], name="head")(x)

    @jax.jit
    def make():
        tree = LM().init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        return from_program_tree(tree)

    return make


def from_program_tree(tree: dict) -> dict:
    """The weights as the reference holds them, from a tree of the program's
    names: a layer's weights under short names, every layer's stacked along a
    leading axis (the scan over the layers takes one slice a layer)."""
    import jax.numpy as jnp

    names = sorted((k for k in tree["stack"] if k.startswith("layer_")), key=lambda k: int(k[6:]))
    norms = {"norm1": "input_norm", "norm2": "attn_out_norm", "norm3": "post_attn_norm", "norm4": "mlp_out_norm"}

    def of_layer(layer: dict) -> dict:
        return {
            **{short: layer[long]["scale"] for short, long in norms.items()},
            **{n: layer["attn"][f"{n}_proj"]["kernel"] for n in ("q", "k", "v", "o")},
            **{n: layer["mlp"][f"{n}_proj"]["kernel"] for n in ("gate", "up", "down")},
        }

    layers = [of_layer(tree["stack"][name]) for name in names]
    return {
        "embed": tree["embed"]["embedding"],
        "norm": tree["stack"]["norm"]["scale"],
        "gate_w": tree["exit_gate"]["kernel"][:, 0],
        "gate_b": tree["exit_gate"]["bias"][0],
        "head": tree["head"]["kernel"],
        "layers": {k: jnp.stack([layer[k] for layer in layers]) for k in layers[0]},
    }


def _divisor_at_most(n: int, limit: float) -> int:
    r = max(1, min(n, int(limit)))
    while n % r:
        r -= 1
    return r


def _layer_functions(shape: tuple, precision: str, r: int, s: int) -> dict:
    """The model's parts for ``r`` rows of ``s`` positions, as plain functions:
    ``rms_norm(x, g)``, ``block(x, w)`` (one layer applied to the stream ``x``
    [R, S, D] with its weights ``w``) and ``mm``."""
    import jax
    import jax.numpy as jnp

    z = _named(shape)
    mm = _matmul(precision)
    hd, nh, eps = z["head_dim"], z["num_attention_heads"], z["rms_norm_eps"]

    def rms_norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g

    # rotary over the whole head: the pair (x[i], x[i + hd/2]) turns by
    # pos * theta^(-2i/hd); the same positions in every pass
    inv_freq = z["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]  # [S, 1, hd/2]

    def rotate(x):  # [R, S, H, hd]
        a, b = x[..., : hd // 2], x[..., hd // 2 :]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    # queries whose scores against all keys are live at once: about 0.27 GB
    q_block = _divisor_at_most(s, 0.27e9 // (r * nh * s * 4))
    key_pos = jnp.arange(s)

    def attention(h, w):
        q = rotate(mm("rsd,de->rse", h, w["q"]).reshape(r, s, nh, hd))
        k = rotate(mm("rsd,de->rse", h, w["k"]).reshape(r, s, nh, hd))
        v = mm("rsd,de->rse", h, w["v"]).reshape(r, s, nh, hd)

        @jax.checkpoint
        def attend(block):
            q_blk, t0 = block  # [R, q_block, H, hd]
            seen = key_pos[None, :] <= t0 + jnp.arange(q_block)[:, None]
            scores = mm("rqhd,rkhd->rhqk", q_blk, k) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return mm("rhqk,rkhd->rqhd", probs, v)

        blocks = q.reshape(r, s // q_block, q_block, nh, hd).swapaxes(0, 1)
        o = jax.lax.map(attend, (blocks, jnp.arange(0, s, q_block)))
        return mm("rse,ed->rsd", o.swapaxes(0, 1).reshape(r, s, nh * hd), w["o"])

    def mlp(h, w):
        hidden = jax.nn.silu(mm("rsd,de->rse", h, w["gate"])) * mm("rsd,de->rse", h, w["up"])
        return mm("rse,ed->rsd", hidden, w["down"])

    def block(x, w):
        a = x + rms_norm(attention(rms_norm(x, w["norm1"]), w), w["norm2"])
        return a + rms_norm(mlp(rms_norm(a, w["norm3"]), w), w["norm4"])

    return {"rms_norm": rms_norm, "block": block, "mm": mm}


def _exit_states(params, tokens, shape: tuple, precision: str, fault: str | None):
    """``z_t`` of every pass, ``[T, R, S, D]``, and the functions they were
    computed with: a loop over the passes and, inside, over the layers, every
    time with the same weights."""
    import jax

    f = _layer_functions(shape, precision, *tokens.shape)
    passes = 1 if fault == "one_pass" else _named(shape)["total_ut_steps"]

    def layer(x, w):
        return jax.checkpoint(f["block"])(x, w), None

    def one_pass(x, _):
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = f["rms_norm"](x, params["norm"])
        return x, x

    _, states = jax.lax.scan(one_pass, params["embed"][tokens], None, length=passes)
    return states, f


def _losses(params, tokens, shape: tuple, precision: str, fault: str | None, objective: bool):
    """The trial's ``loss`` (``objective``: the expected-exit objective) or its
    ``eval_loss`` (the last exit's mean cross entropy) of ``tokens`` [R, S];
    the head and the cross entropy in blocks of positions (a block's float32
    logits near 0.3 GB)."""
    import jax
    import jax.numpy as jnp

    r, s = tokens.shape
    beta = _named(shape)["exit_beta"]
    states, f = _exit_states(params, tokens, shape, precision, fault)
    # position t predicts token t+1; the last position predicts nothing
    targets = jnp.concatenate([tokens[:, 1:], jnp.zeros((r, 1), tokens.dtype)], axis=1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
    block = _divisor_at_most(s, 0.3e9 // (r * params["head"].shape[1] * 4))
    split = lambda a: jnp.moveaxis(a.reshape(r, s // block, block, *a.shape[2:]), 1, 0)  # noqa: E731

    @jax.checkpoint
    def block_nll(args):
        x_blk, target = args  # [R, block, D], [R, block]
        logp = jax.nn.log_softmax(f["mm"]("rsd,dv->rsv", x_blk, params["head"]), axis=-1)
        return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]

    def token_nll(x):  # [R, S, D] -> [R, S], 0 where there is no next token
        nll = jax.lax.map(block_nll, (split(x), split(targets)))
        return jnp.moveaxis(nll, 0, 1).reshape(r, s) * counted

    mean = lambda a: jnp.sum(a * counted) / (r * (s - 1))  # noqa: E731
    if not objective:
        return mean(token_nll(states[-1]))
    # a token's exit distribution in log space: log q_t = log sigmoid(a_t) +
    # sum_{j<t} log sigmoid(-a_j).  A gate that ten updates at the highest
    # rate have saturated (sigmoid(a) rounds to 1 in float32) then leaves the
    # later exits a tiny share and a finite q log q, where 1 - lambda gives 0
    # and a gradient of 0 x inf
    expected = entropy = 0.0
    log_inside = jnp.zeros((r, s), jnp.float32)  # log of the share still inside before exit t
    for t, x in enumerate(states):
        if fault == "even_exits":
            log_q = jnp.full((r, s), -math.log(len(states)))
        elif t < len(states) - 1:
            a = f["mm"]("rsd,d->rs", x, params["gate_w"]) + params["gate_b"]
            log_q, log_inside = jax.nn.log_sigmoid(a) + log_inside, jax.nn.log_sigmoid(-a) + log_inside
        else:
            log_q = log_inside
        q = jnp.exp(log_q)
        expected = expected + q * token_nll(x)
        entropy = entropy - q * log_q
    return mean(expected - beta * entropy)


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
        return _losses(params, tokens, shape, precision, fault, objective=True)

    @jax.jit
    def eval_loss(params, tokens):
        last = lambda t: _losses(params, t, shape, precision, fault, objective=False)  # noqa: E731
        return jnp.mean(jax.lax.map(last, blocks_of(tokens)))

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
    inside ``_losses``); keep a block near 2 GB.  At the cell's sizes that is
    the one row."""
    per_row = sizes["seq_len"] * 4 * (8 * sizes["hidden_size"] + 3 * sizes["intermediate_size"])
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
