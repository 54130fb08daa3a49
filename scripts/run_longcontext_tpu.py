"""Long-context attention benchmark on the local accelerator.

Times the fused flash-attention Pallas kernel (fwd + bwd through the
custom_vjp) against the dense reference at sequence lengths where dense
attention's O(S^2) materialization starts to hurt, and records achieved
tokens/sec for a TransformerLM training step with ring attention over a
sequence-parallel mesh (single chip: mesh degenerates to 1, exercising the
same code path the v5e-8 run shards).

This capability exceeds the reference (kubeflow/katib has no long-context
anything — SURVEY §5 "absent"); the artifact
``artifacts/longcontext/bench.json`` is the evidence it works at speed on
the hardware.

Env knobs: LC_SEQ (default 4096), LC_BATCH (4), LC_STEPS (10),
LC_SMALL=1 (CPU smoke: tiny shapes, interpret-mode kernel).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import setup_jax, write_artifact  # noqa: E402


def main() -> int:
    jax = setup_jax(compile_cache=True)
    import jax.numpy as jnp

    small = os.environ.get("LC_SMALL", "") not in ("", "0")
    seq = int(os.environ.get("LC_SEQ", "256" if small else "4096"))
    batch = int(os.environ.get("LC_BATCH", "1" if small else "4"))
    steps = int(os.environ.get("LC_STEPS", "2" if small else "10"))
    heads, d_head = (2, 32) if small else (8, 64)
    platform = jax.devices()[0].platform
    interpret = platform != "tpu"

    from katib_tpu.ops.flash_attention import flash_attention, reference_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (batch, heads, seq, d_head)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    def timed_chain(update_fn, init_carry):
        """Avg seconds per update, measured as ONE jitted lax.scan dispatch.

        Two measured failure modes of naive timing on a remote chip,
        both producing physically impossible numbers (first cuts of this
        script recorded 7.5 and 27.9 PFLOP/s on a 197 TFLOP/s chip):

        - independent back-to-back dispatches of the same executable don't
          serialize — fixed by the scan chain (each step consumes the
          previous step's output, so the work cannot be elided or
          overlapped);
        - re-invoking an executable on the SAME input buffers can resolve
          from the previous result's already-ready buffers without a fresh
          execution, so even ``block_until_ready`` returns in microseconds
          — fixed by bumping the carry through a jitted identity-valued op
          with a fresh scalar operand (new device buffers, same values)
          before the timed rep, and by fetching a reduced scalar to the
          host, which forces real bytes computed on the chip.
        """

        @jax.jit
        def many(carry):
            return jax.lax.scan(
                lambda c, _: (update_fn(c), None), carry, None, length=steps
            )[0]

        @jax.jit
        def bump(carry, i):
            z = jnp.float32(i) * 0.0
            return jax.tree.map(lambda a: a + z.astype(a.dtype), carry)

        @jax.jit
        def redsum(carry):
            return sum(
                jnp.sum(a.astype(jnp.float32)) for a in jax.tree.leaves(carry)
            )

        float(redsum(many(bump(init_carry, 1))))  # compile + warm everything
        fresh = bump(init_carry, 2)
        jax.block_until_ready(fresh)
        t0 = time.perf_counter()
        out = many(fresh)
        float(redsum(out))  # real bytes off the chip end the clock
        return (time.perf_counter() - t0) / steps

    def eps_sgd(grad_fn, eps=1e-3):
        """Chainable update: epsilon-SGD keeps values bounded while forcing
        true data dependence between scan iterations (eps=0 would let XLA
        drop the whole gradient computation as dead code)."""

        def update(carry):
            _, grads = grad_fn(*carry)
            return tuple(
                a - jnp.asarray(eps, a.dtype) * g for a, g in zip(carry, grads)
            )

        return update

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret).astype(
            jnp.float32
        ).sum()

    fwd_bwd_s = timed_chain(
        eps_sgd(jax.value_and_grad(loss_flash, argnums=(0, 1, 2))), (q, k, v)
    )

    def loss_dense(q, k, v):
        return reference_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    dense_s = timed_chain(
        eps_sgd(jax.value_and_grad(loss_dense, argnums=(0, 1, 2))), (q, k, v)
    )

    # causal attention FLOPs: ~2 * 0.5*S^2 * d * B * H for QK^T, same for PV,
    # and ~2.5x forward for the backward pass
    attn_flops = 2 * 2 * 0.5 * seq * seq * d_head * batch * heads
    total_flops = attn_flops * 3.5
    tokens_per_sec = batch * seq / fwd_bwd_s
    tflops = total_flops / fwd_bwd_s / 1e12
    # physical upper bound per chip generation (bf16 dense peak) — any
    # number above it means the harness, not the kernel, is being measured
    # (the shared table, keyed by the live device's kind; none on the CPU)
    from katib_tpu.costmodel.peaks import peaks_for

    peak_tflops = peaks_for().peak_flops("bf16") / 1e12 if platform == "tpu" else None
    sane = platform != "tpu" or tflops < peak_tflops * 1.05

    result = {
        "platform": platform,
        "kernel": "pallas" if not interpret else "pallas-interpret",
        "seq_len": seq,
        "batch": batch,
        "heads": heads,
        "d_head": d_head,
        "fwd_bwd_step_s": round(fwd_bwd_s, 6),
        "dense_fwd_bwd_step_s": round(dense_s, 6),
        "flash_speedup_vs_dense": round(dense_s / fwd_bwd_s, 3),
        "attention_tokens_per_sec": round(tokens_per_sec, 1),
        "attention_tflops": round(tflops, 3),
        "sanity": {"peak_tflops_bf16": peak_tflops, "below_peak": sane},
    }
    if not sane:
        print(
            f"longcontext: MEASUREMENT INSANE ({tflops:.0f} TFLOP/s > chip "
            f"peak {peak_tflops}); refusing to write the artifact",
            file=sys.stderr,
        )
        print(json.dumps(result), flush=True)
        return 1

    # the same kernel inside a training step of the long-context LM with the
    # ring-attention path (axis size 1 on a single chip — identical code to
    # the sharded run, collective count 0)
    if not small:
        from katib_tpu.models.transformer import TransformerLM, lm_loss, markov_dataset

        model = TransformerLM(
            vocab_size=256, d_model=heads * d_head, n_heads=heads, n_layers=4,
            max_seq_len=seq,
        )
        tokens = jnp.asarray(markov_dataset(256, batch, seq, seed=0))
        params = model.init(jax.random.PRNGKey(1), tokens)

        def lm_step(p, toks):
            return lm_loss(model.apply(p, toks), toks)

        lm_grad = jax.grad(lm_step)

        def lm_update(p):
            # same eps-SGD chaining trick as eps_sgd(), over a pytree carry
            g = lm_grad(p, tokens)
            return jax.tree.map(lambda w, gw: w - 1e-4 * gw, p, g)

        lm_s = timed_chain(lm_update, params)
        result["lm_train_tokens_per_sec"] = round(batch * seq / lm_s, 1)
        result["lm_step_s"] = round(lm_s, 6)

    write_artifact("longcontext", "bench.json", result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
