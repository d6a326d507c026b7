"""Perf probe: ablate batch size / attention impl / precision knobs on the
real chip to find where the flagship bench step time goes.

Usage: python scripts/perf_probe.py [probe ...]
Probes: batch attn fwdbwd opt
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.common.platform import configure_compile_cache
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.telemetry.costmodel import build_train_program

SEQ = 1024


def base_cfg(**kw):
    d = dict(
        vocab_size=32000,
        hidden_size=768,
        intermediate_size=2048,
        num_layers=12,
        num_heads=12,
        num_kv_heads=12,
        max_seq_len=SEQ,
        attention_impl="flash",
        flash_block_kv=1024,
    )
    d.update(kw)
    return LlamaConfig(**d)


def time_step(cfg, batch, steps=20, label="", opt=None):
    model = LlamaModel(cfg)
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    rules = PRESET_RULES["dp"]
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, SEQ + 1))
    sample = {
        "input_ids": jnp.asarray(ids[:, :-1], jnp.int32),
        "labels": jnp.asarray(ids[:, 1:], jnp.int32),
    }
    if opt is None:
        opt = optax.chain(
            optax.clip_by_global_norm(1.0), optax.adamw(3e-4, b2=0.95)
        )
    # One build path with bench.py / the AOT pipeline (telemetry/costmodel).
    state, step_fn, sample = build_train_program(
        model, opt, mesh, rules, sample
    )
    state, metrics = step_fn(state, sample)
    float(metrics["loss"])  # sync
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, sample)
    float(metrics["loss"])
    dt = time.perf_counter() - t0
    tps = batch * SEQ * steps / dt
    print(f"{label:40s} batch={batch:3d} {dt/steps*1000:7.2f} ms/step "
          f"{tps:10,.0f} tok/s", flush=True)
    return tps


def probe_batch():
    for b in (8, 16, 32, 64):
        try:
            time_step(base_cfg(), b, label="flash kv1024")
        except Exception as e:
            print(f"batch={b} failed: {type(e).__name__}: {e}", flush=True)


def probe_attn():
    for impl, kw in (
        ("dot", {}),
        ("flash", {"flash_block_kv": 512}),
        ("flash", {"flash_block_kv": 1024}),
        ("flash", {"flash_block_q": 1024, "flash_block_kv": 1024}),
    ):
        try:
            time_step(base_cfg(attention_impl=impl, **kw), 8,
                      label=f"attn={impl} {kw}")
        except Exception as e:
            print(f"attn={impl} {kw} failed: {type(e).__name__}: {e}",
                  flush=True)


def probe_fwdbwd():
    """Forward-only vs fwd+bwd vs full step, to locate optimizer overhead."""
    cfg = base_cfg()
    batch = 8
    model = LlamaModel(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, SEQ + 1))
    x = jnp.asarray(ids[:, :-1], jnp.int32)
    y = jnp.asarray(ids[:, 1:], jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), x)

    from dlrover_tpu.models.llama import cross_entropy_loss

    def loss_fn(p):
        return cross_entropy_loss(model.apply(p, x), y)

    fwd = jax.jit(loss_fn)
    vg = jax.jit(lambda p: jax.value_and_grad(loss_fn)(p))

    for name, fn, sync in (
        ("fwd only", fwd, lambda r: float(r)),
        ("fwd+bwd", vg, lambda r: float(r[0])),
    ):
        fn_out = fn(params)
        sync(fn_out)
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(params)
        sync(out)
        dt = (time.perf_counter() - t0) / 20
        print(f"{name:40s} batch={batch:3d} {dt*1000:7.2f} ms", flush=True)


def probe_splash():
    for bq, bkv in ((512, 512), (512, 1024), (1024, 1024), (256, 512)):
        try:
            time_step(
                base_cfg(attention_impl="splash", flash_block_q=bq,
                         flash_block_kv=bkv),
                8, label=f"splash q{bq} kv{bkv}",
            )
        except Exception as e:
            print(f"splash q{bq} kv{bkv} failed: {type(e).__name__}: {e}",
                  flush=True)


def probe_combo():
    time_step(
        base_cfg(attention_impl="splash", flash_block_q=512,
                 flash_block_kv=512, scan_layers=False),
        8, label="splash+unrolled",
    )
    time_step(
        base_cfg(attention_impl="splash", flash_block_q=512,
                 flash_block_kv=512, scan_layers=False,
                 logits_f32_output=False),
        8, label="splash+unrolled+bf16logits",
    )
    time_step(
        base_cfg(scan_layers=False, logits_f32_output=False),
        8, label="flash+unrolled+bf16logits",
    )


def probe_longseq():
    """Long-context single-chip: same token budget (8192 tok/step) at
    growing sequence lengths; splash keeps the O(s^2) score tensor out of
    HBM so throughput should degrade only with attention FLOPs."""
    global SEQ
    base = dict(attention_impl="splash", flash_block_q=512,
                flash_block_kv=512, scan_layers=False,
                logits_f32_output=False)
    for seq, batch in ((1024, 8), (2048, 4), (4096, 2), (8192, 1)):
        SEQ = seq
        try:
            time_step(
                base_cfg(max_seq_len=seq, **base), batch,
                label=f"seq={seq}",
            )
        except Exception as e:
            print(f"seq={seq} failed: {type(e).__name__}: {e}", flush=True)
    SEQ = 1024


def probe_combo2():
    """Sweep batch + splash blocks under the shipped config
    (unrolled layers, bf16 logits)."""
    best = dict(attention_impl="splash", scan_layers=False,
                logits_f32_output=False)
    for b in (8, 16):
        time_step(
            base_cfg(flash_block_q=512, flash_block_kv=512, **best),
            b, label="splash512 unrolled",
        )
    for bq, bkv in ((1024, 1024), (256, 256), (512, 256)):
        time_step(
            base_cfg(flash_block_q=bq, flash_block_kv=bkv, **best),
            8, label=f"splash q{bq} kv{bkv} unrolled",
        )


def probe_scan():
    time_step(base_cfg(), 8, label="scan_layers=True (current)")
    time_step(base_cfg(scan_layers=False), 8, label="scan_layers=False")


def probe_logits():
    time_step(base_cfg(), 8, label="logits f32 out (current)")
    time_step(base_cfg(logits_f32_output=False), 8, label="logits bf16 out")


def probe_opt():
    """Optimizer-only cost: apply_gradients with dummy grads."""
    cfg = base_cfg()
    model = LlamaModel(cfg)
    x = jnp.zeros((1, SEQ), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), x)["params"]
    for name, opt in (
        ("adamw+clip", optax.chain(optax.clip_by_global_norm(1.0),
                                   optax.adamw(3e-4, b2=0.95))),
        ("adamw", optax.adamw(3e-4, b2=0.95)),
    ):
        opt_state = opt.init(params)
        grads = jax.tree.map(jnp.ones_like, params)

        @jax.jit
        def upd(p, s, g):
            u, s2 = opt.update(g, s, p)
            return optax.apply_updates(p, u), s2

        p2, s2 = upd(params, opt_state, grads)
        jax.block_until_ready(jax.tree.leaves(p2)[0])
        t0 = time.perf_counter()
        for _ in range(50):
            p2, s2 = upd(p2, s2, grads)
        float(jax.tree.leaves(p2)[0][0, 0])
        dt = (time.perf_counter() - t0) / 50
        print(f"opt {name:36s} {dt*1000:7.2f} ms", flush=True)




def probe_longblocks():
    """Splash block sweep at 4k/8k (round-2 verdict: attention-inclusive
    MFU sagged at long seq — is there block-size headroom?)."""
    global SEQ
    base = dict(attention_impl="splash", scan_layers=False,
                logits_f32_output=False)
    for seq, batch in ((4096, 2), (8192, 1)):
        SEQ = seq
        for bq, bkv in ((512, 512), (1024, 1024), (2048, 2048)):
            try:
                time_step(
                    base_cfg(max_seq_len=seq, flash_block_q=bq,
                             flash_block_kv=bkv, **base),
                    batch, label=f"seq={seq} splash q{bq} kv{bkv}",
                )
            except Exception as e:
                print(f"seq={seq} q{bq}/kv{bkv} failed: "
                      f"{type(e).__name__}: {e}", flush=True)
    SEQ = 1024


def probe_int8_batch():
    """int8 optimizer states free ~0.8 GB HBM (adam m+v: 1.07 GB f32 ->
    ~0.28 GB int8+scales): does a larger batch now pay at s=1024?
    (round-2: b16 was 4% slower, b32 failed remote compile — memory was
    not the binding constraint, but re-check with the quantized chain.)
    Same weight decay as the adamw baseline: optimizer-for-optimizer."""
    from dlrover_tpu.optimizers.quantized import quantized_adamw

    best = dict(attention_impl="splash", flash_block_q=512,
                flash_block_kv=512, scan_layers=False,
                logits_f32_output=False)
    opt = optax.chain(
        optax.clip_by_global_norm(1.0),
        quantized_adamw(3e-4, b2=0.95, weight_decay=1e-4),
    )
    for b in (8, 16, 24):
        try:
            time_step(base_cfg(**best), b, label="int8-adam", opt=opt)
        except Exception as e:
            print(f"int8 batch={b} failed: {type(e).__name__}: {e}",
                  flush=True)


def probe_wide():
    """Settle the 'shape-bound, not framework-bound' MFU-ceiling claim
    (round-3 weak #5): a llama-7B-width single layer should tile far
    better on the MXU than GPT-2-small's 768-wide GEMMs.  One layer,
    same step machinery — any MFU jump is the shapes, not the framework."""
    for hidden, inter, heads, batch in (
        (768, 2048, 12, 8),     # GPT-2-small width (baseline)
        (2048, 5504, 16, 4),    # mid
        (4096, 11008, 32, 2),   # llama-7B width
    ):
        cfg = base_cfg(
            hidden_size=hidden, intermediate_size=inter,
            num_heads=heads, num_kv_heads=heads, num_layers=1,
            attention_impl="splash", flash_block_q=512,
            flash_block_kv=512, scan_layers=False,
            logits_f32_output=False, vocab_size=8192,
        )
        tps = time_step(cfg, batch, label=f"1-layer hidden={hidden}")
        # MFU vs v5e peak, counting only this model's params
        model = LlamaModel(cfg)
        n_params = sum(
            int(np.prod(x.shape))
            for x in jax.tree.leaves(jax.eval_shape(
                model.init, jax.random.key(0),
                jnp.zeros((1, 8), jnp.int32),
            ))
        )
        mfu = 6 * n_params * tps / 197e12
        print(f"    -> params {n_params/1e6:.1f}M  MFU~{mfu:.3f} "
              f"(param-flops only, attn excluded)", flush=True)


def probe_fusedce():
    """Chunked head+CE (ops/chunked_ce.py) vs materialized logits at bench
    scale: does skipping the 0.5 GB logits round-trip pay on-chip, and at
    what chunk count?  Also probed at 8k (logits memory scales with b*s)."""
    global SEQ
    best = dict(attention_impl="splash", scan_layers=False,
                logits_f32_output=False)
    for seq, batch in ((1024, 8), (8192, 2)):
        SEQ = seq
        try:
            time_step(base_cfg(max_seq_len=seq, **best), batch,
                      label=f"s{seq} unfused baseline")
        except Exception as e:
            print(f"s{seq} baseline failed: {type(e).__name__}: {e}",
                  flush=True)
        for chunks in (4, 8, 16):
            try:
                time_step(
                    base_cfg(max_seq_len=seq, fused_ce_chunks=chunks,
                             **best),
                    batch, label=f"s{seq} fused-ce c{chunks}",
                )
            except Exception as e:
                print(f"s{seq} fused c{chunks} failed: "
                      f"{type(e).__name__}: {e}", flush=True)
    SEQ = 1024


def probe_fp8():
    """fp8 matmul path at bench scale: dynamic vs delayed scaling vs
    bf16 baseline (v5e has no native fp8 MXU mode — this measures the
    cast/scale overhead; v5p+/Trillium get the 2x rate)."""
    best = dict(attention_impl="splash", flash_block_q=512,
                flash_block_kv=512, scan_layers=False,
                logits_f32_output=False)
    time_step(base_cfg(**best), 8, label="bf16 baseline")
    for scaling in ("dynamic", "delayed"):
        try:
            time_step(
                base_cfg(use_fp8=True, fp8_scaling=scaling, **best),
                8, label=f"fp8 {scaling}",
            )
        except Exception as e:
            print(f"fp8 {scaling} failed: {type(e).__name__}: {e}",
                  flush=True)


if __name__ == "__main__":
    probes = sys.argv[1:] or ["fwdbwd", "opt", "attn", "batch"]
    configure_compile_cache()
    print(f"devices: {jax.devices()}", flush=True)
    for p in probes:
        globals()[f"probe_{p}"]()
