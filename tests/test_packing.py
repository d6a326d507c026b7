"""Packed long-context training: sequence packer properties, segment-sparse
attention no-leak guarantees across every attention path (reference, in-tree
flash, splash interpret, ring, ulysses), boundary-loss masking, and the
mask-aware cost model.

All tests run on the 8-device virtual CPU mesh; the splash kernel runs in
interpret mode (head_dim=128, its unconditional lane requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.data.packing import (
    SequencePacker,
    lm_batch_from_rows,
    pack_documents,
    packed_lm_batches,
    segment_histogram,
    segment_lengths,
)
from dlrover_tpu.ops.flash_attention import flash_attention_gqa, mha_reference
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from dlrover_tpu.parallel.ring_attention import ring_attention
from dlrover_tpu.parallel.sharding import PRESET_RULES
from dlrover_tpu.parallel.ulysses import ulysses_attention

pytestmark = pytest.mark.packing


def _docs(lengths, base=1):
    """One doc per length; doc i is filled with value base+i so packed
    rows can be traced back to their source documents exactly."""
    return [np.full((n,), base + i, np.int32) for i, n in enumerate(lengths)]


def _naive_segmented(q, k, v, seg):
    """Dense masked softmax oracle: causal AND same-segment."""
    group = q.shape[2] // k.shape[2]
    if group != 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    causal = np.tril(np.ones((s, s), bool))
    same = np.asarray(seg)[:, :, None] == np.asarray(seg)[:, None, :]
    mask = jnp.asarray(causal[None, None] & same[:, None])
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def _rand_packed(b=2, s=256, h=4, h_kv=2, d=64, seed=0, doc_len=(40, 96)):
    """Random q/k/v plus a packed-style segment layout (tail padding)."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h_kv, d)), jnp.float32)
    seg = np.zeros((b, s), np.int32)
    for row in range(b):
        off, i = 0, 1
        while off < s - doc_len[0]:
            n = int(rng.randint(*doc_len))
            n = min(n, s - off)
            seg[row, off : off + n] = i
            off += n
            i += 1
        # leave the tail as padding (segment 0) on odd rows
        if row % 2 == 0 and off < s:
            seg[row, off:] = i
    return q, k, v, jnp.asarray(seg)


class TestPackerProperties:
    def test_no_token_loss_positions_and_segments(self):
        lengths = [40, 100, 60, 28, 120, 7, 99, 64, 33, 80]
        docs = _docs(lengths)
        rows = list(pack_documents(docs, seq_len=128))
        # Every input token appears exactly once across all rows.
        assert sum(r.real_tokens for r in rows) == sum(lengths)
        seen = {}
        for r in rows:
            for seg_id in np.unique(r.segment_ids[r.segment_ids > 0]):
                sel = r.segment_ids == seg_id
                toks = r.tokens[sel]
                # Doc value encodes identity; a doc is contiguous+constant.
                assert len(np.unique(toks)) == 1
                val = int(toks[0])
                seen[val] = seen.get(val, 0) + len(toks)
                # RoPE positions reset to 0 at each document start.
                np.testing.assert_array_equal(
                    r.positions[sel], np.arange(len(toks))
                )
            # Segment ids are 1-based and consecutive within a row.
            ids = np.unique(r.segment_ids[r.segment_ids > 0])
            np.testing.assert_array_equal(ids, np.arange(1, len(ids) + 1))
            # Padding is all-zero tokens/positions/segments at the tail.
            pad = r.segment_ids == 0
            assert (r.tokens[pad] == 0).all()
        assert seen == {1 + i: n for i, n in enumerate(lengths)}

    def test_overlong_doc_splits_into_chunks(self):
        packer = SequencePacker(seq_len=64)
        rows = list(packer.add(np.full((160,), 7, np.int32)))
        rows += list(packer.flush())
        assert packer.stats.split_docs == 1
        # 160 = 64 + 64 + 32: each chunk its own segment.
        assert sorted(
            n for r in rows for n in r.doc_lengths
        ) == [32, 64, 64]

    def test_fifo_eviction_bounds_open_bins(self):
        packer = SequencePacker(seq_len=100, open_bins=2)
        emitted = []
        for n in (60, 70, 80):  # none fit together
            emitted += list(packer.add(np.ones((n,), np.int32)))
        # Third doc forced the oldest (60) bin out.
        assert len(emitted) == 1 and emitted[0].doc_lengths == [60]
        assert len(packer._bins) <= 2
        emitted += list(packer.flush())
        assert sum(r.real_tokens for r in emitted) == 60 + 70 + 80

    def test_mean1k_mixture_efficiency(self):
        rng = np.random.RandomState(0)
        mu = np.log(1024) - 0.5
        docs = (
            np.ones((max(16, min(int(n), 8192)),), np.int32)
            for n in rng.lognormal(mu, 1.0, size=80)
        )
        rows = list(pack_documents(docs, seq_len=8192))
        real = sum(r.real_tokens for r in rows)
        assert real / (len(rows) * 8192) >= 0.9

    def test_lm_batch_boundary_mask(self):
        rows = list(pack_documents(_docs([5, 3]), seq_len=10))
        batch = lm_batch_from_rows(rows)
        assert batch["input_ids"].shape == (1, 10)
        seg = batch["segment_ids"][0]
        np.testing.assert_array_equal(
            seg, [1, 1, 1, 1, 1, 2, 2, 2, 0, 0]
        )
        # labels shift within a doc; the boundary-loss mask zeroes the
        # last token of each doc and all padding.
        np.testing.assert_array_equal(
            batch["mask"][0], [1, 1, 1, 1, 0, 1, 1, 0, 0, 0]
        )
        np.testing.assert_array_equal(
            batch["labels"][0][:4], batch["input_ids"][0][1:5]
        )
        assert (batch["labels"][0][batch["mask"][0] == 0] == 0).all()

    def test_packed_lm_batches_stream(self):
        docs = _docs([30, 50, 20, 70, 40, 10])
        batches = list(packed_lm_batches(docs, seq_len=64, batch_size=2))
        assert batches
        for b in batches:
            assert set(b) == {
                "input_ids", "labels", "mask", "positions", "segment_ids"
            }
            assert b["input_ids"].shape[1] == 64


class TestSegmentedReference:
    def test_matches_naive_dense_mask(self):
        q, k, v, seg = _rand_packed(s=128)
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        naive = _naive_segmented(q, k, v, seg)
        np.testing.assert_allclose(ref, naive, atol=2e-5, rtol=2e-5)

    def test_chunked_path_matches(self):
        q, k, v, seg = _rand_packed(s=128)
        # q_chunk < s forces the lax.map chunked path.
        out = mha_reference(q, k, v, causal=True, segment_ids=seg, q_chunk=32)
        naive = _naive_segmented(q, k, v, seg)
        np.testing.assert_allclose(out, naive, atol=2e-5, rtol=2e-5)

    def test_matches_per_document_attention(self):
        """Gold standard: each packed document attends exactly as it
        would unpacked — positions sliced out per doc."""
        q, k, v, seg = _rand_packed(b=1, s=128)
        out = mha_reference(q, k, v, causal=True, segment_ids=seg)
        for seg_id in np.unique(np.asarray(seg)[0]):
            if seg_id == 0:
                continue
            sel = np.asarray(seg)[0] == seg_id
            solo = mha_reference(
                q[:, sel], k[:, sel], v[:, sel], causal=True
            )
            np.testing.assert_allclose(
                np.asarray(out)[0, sel], np.asarray(solo)[0],
                atol=2e-5, rtol=2e-5,
            )


class TestFlashSegmented:
    def test_forward_matches_segmented_reference(self):
        q, k, v, seg = _rand_packed(s=256)
        out = jax.jit(
            lambda *a: flash_attention_gqa(*a, block_q=64, block_kv=64)
        )(q, k, v, seg)
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_no_leak_across_documents(self):
        """Perturbing document 1 must leave document 2's output
        bit-identical — the kernel's segment predicate, not a soft mask."""
        q, k, v, seg = _rand_packed(b=1, s=128)
        fn = jax.jit(
            lambda *a: flash_attention_gqa(*a, block_q=64, block_kv=64)
        )
        base = fn(q, k, v, seg)
        sel1 = np.asarray(seg)[0] == 1
        sel2 = np.asarray(seg)[0] == 2
        assert sel1.any() and sel2.any()
        k2 = k.at[:, np.flatnonzero(sel1)[0]].add(100.0)
        pert = fn(q, k2, v, seg)
        assert np.array_equal(
            np.asarray(base)[0, sel2], np.asarray(pert)[0, sel2]
        )

    def test_grads_match_segmented_reference(self):
        q, k, v, seg = _rand_packed(s=128)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2
            )

        flash = lambda q, k, v: flash_attention_gqa(
            q, k, v, seg, block_q=64, block_kv=64
        )
        ref = lambda q, k, v: mha_reference(
            q, k, v, causal=True, segment_ids=seg
        )
        g1 = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)

    def test_non_segmented_regression(self):
        q, k, v = _rand_packed(s=256)[:3]
        out = jax.jit(
            lambda *a: flash_attention_gqa(*a, block_q=128, block_kv=128)
        )(q, k, v)
        np.testing.assert_allclose(
            out, mha_reference(q, k, v), atol=2e-5, rtol=2e-5
        )


class TestSplashSegmented:
    """The library splash kernel must run packed rows through its native
    SegmentIds argument — NOT fall back — whenever shapes tile
    (head_dim % 128, the kernel's unconditional lane requirement).
    Interpret mode stands in for the TPU on CPU CI."""

    def _qkv(self, b=1, s=512, h=2, d=128):
        q, k, v, seg = _rand_packed(
            b=b, s=s, h=h, h_kv=h, d=d, doc_len=(64, 160)
        )
        return q, k, v, seg

    def test_kernel_runs_with_segment_ids_no_fallback(self, monkeypatch):
        from dlrover_tpu.ops import splash_attention as sa

        monkeypatch.setattr(
            sa, "_record_fallback",
            lambda reason: pytest.fail(
                f"splash fell back (reason={reason}) on a tileable "
                f"segmented shape"
            ),
        )
        q, k, v, seg = self._qkv()
        out = sa.splash_attention_gqa(
            q, k, v, seg, block_q=512, block_kv=512, interpret=True
        )
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_no_leak_across_documents(self):
        from dlrover_tpu.ops.splash_attention import splash_attention_gqa

        q, k, v, seg = self._qkv()
        fn = lambda k_: splash_attention_gqa(
            q, k_, v, seg, block_q=512, block_kv=512, interpret=True
        )
        base = fn(k)
        sel1 = np.asarray(seg)[0] == 1
        sel2 = np.asarray(seg)[0] == 2
        k2 = k.at[:, np.flatnonzero(sel1)[0]].add(100.0)
        pert = fn(k2)
        assert np.array_equal(
            np.asarray(base)[0, sel2], np.asarray(pert)[0, sel2]
        )

    def test_max_segment_len_band_is_exact(self):
        """The packer-bound LocalMask band is a static superset of the
        segment mask: pruned blocks were all-masked anyway, so results
        are identical with and without the bound."""
        from dlrover_tpu.ops.splash_attention import splash_attention_gqa

        q, k, v, seg = self._qkv()
        full = splash_attention_gqa(
            q, k, v, seg, block_q=512, block_kv=512, interpret=True
        )
        banded = splash_attention_gqa(
            q, k, v, seg, block_q=512, block_kv=512,
            max_segment_len=256, interpret=True,
        )
        np.testing.assert_allclose(banded, full, atol=1e-6, rtol=1e-6)

    def test_head_dim_64_reaches_the_library_kernel(self, monkeypatch):
        """The head dim is not gated: the installed kernel pads it (and
        the v5e compiler accepts d=64 — tests/test_chip_compile.py), so a
        d=64 model runs the library kernel, segmented, with no fallback."""
        from dlrover_tpu.ops import splash_attention as sa

        monkeypatch.setattr(
            sa, "_record_fallback",
            lambda reason: pytest.fail(f"d=64 fell back ({reason})"),
        )
        q, k, v, seg = self._qkv(d=64)
        out = sa.splash_attention_gqa(
            q, k, v, seg, block_q=512, block_kv=512, interpret=True
        )
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)

    def test_fallback_records_counter(self):
        from dlrover_tpu.ops.splash_attention import splash_attention_gqa
        from dlrover_tpu.telemetry.metrics import render_metrics

        # CPU backend without interpret: must fall back AND count it.
        q, k, v = _rand_packed(s=256)[:3]
        out = splash_attention_gqa(q, k, v, block_q=128, block_kv=128)
        np.testing.assert_allclose(
            out, mha_reference(q, k, v), atol=2e-5, rtol=2e-5
        )
        text = render_metrics()
        assert 'dlrover_attention_fallback_total{reason="backend"}' in text


class TestShardedSegmented:
    @pytest.fixture()
    def mesh(self, devices8):
        return build_mesh(MeshConfig(dp=2, sp=4), devices8)

    def test_ring_matches_segmented_reference(self, mesh):
        q, k, v, seg = _rand_packed(s=256)
        with use_mesh(mesh):
            out = jax.jit(ring_attention)(q, k, v, seg)
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ring_grads_match(self, mesh):
        q, k, v, seg = _rand_packed(s=128)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v).astype(jnp.float32) ** 2
            )

        ring = lambda q, k, v: ring_attention(q, k, v, seg)
        ref = lambda q, k, v: mha_reference(
            q, k, v, causal=True, segment_ids=seg
        )
        with use_mesh(mesh):
            g1 = jax.jit(jax.grad(loss(ring), argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)

    def test_ulysses_matches_segmented_reference(self, mesh):
        q, k, v, seg = _rand_packed(s=256, h=4, h_kv=4)
        with use_mesh(mesh):
            out = jax.jit(
                lambda *a: ulysses_attention(*a, use_flash=False)
            )(q, k, v, seg)
        ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestModelNoLeak:
    def test_llama_packed_documents_independent(self):
        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        model = LlamaModel(cfg)
        batch = next(
            packed_lm_batches(_docs([20, 24, 18]), seq_len=64, batch_size=1)
        )
        ids = jnp.asarray(batch["input_ids"])
        pos = jnp.asarray(batch["positions"])
        seg = jnp.asarray(batch["segment_ids"])
        params = jax.jit(model.init)(jax.random.key(0), ids)
        apply = jax.jit(model.apply)
        base = apply(params, ids, pos, seg)
        sel1 = np.asarray(seg)[0] == 1
        sel2 = np.asarray(seg)[0] == 2
        ids2 = ids.at[0, np.flatnonzero(sel1)[0]].set(
            (int(ids[0, 0]) + 1) % cfg.vocab_size
        )
        pert = apply(params, ids2, pos, seg)
        # Doc 2's logits are BIT-identical: no leak through attention,
        # RoPE, or norm statistics.
        assert np.array_equal(
            np.asarray(base)[0, sel2], np.asarray(pert)[0, sel2]
        )

    def test_glm_segment_ids_in_prefix_slot(self):
        from dlrover_tpu.models.glm import GLMConfig, GLMModel

        cfg = GLMConfig.tiny(dtype=jnp.float32)
        model = GLMModel(cfg)
        batch = next(
            packed_lm_batches(_docs([20, 24, 18]), seq_len=64, batch_size=1)
        )
        ids = jnp.asarray(batch["input_ids"])
        pos = jnp.asarray(batch["positions"])
        seg = jnp.asarray(batch["segment_ids"])
        params = jax.jit(model.init)(jax.random.key(0), ids)
        apply = jax.jit(
            lambda p, i, s: model.apply(p, i, positions=pos, prefix_len=s)
        )
        base = apply(params, ids, seg)
        sel1 = np.asarray(seg)[0] == 1
        sel2 = np.asarray(seg)[0] == 2
        ids2 = ids.at[0, np.flatnonzero(sel1)[0]].set(
            (int(ids[0, 0]) + 1) % cfg.vocab_size
        )
        pert = apply(params, ids2, seg)
        assert np.array_equal(
            np.asarray(base)[0, sel2], np.asarray(pert)[0, sel2]
        )


class TestPackedTrainStep:
    def test_step_runs_and_masks_boundaries(self, devices8):
        import optax

        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
        from dlrover_tpu.trainer.step import (
            create_sharded_state,
            data_sharding,
            make_train_step,
        )

        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        model = LlamaModel(cfg)
        mesh = build_mesh(MeshConfig(dp=2), devices8[:2])
        rules = PRESET_RULES["dp"]
        docs = _docs([30, 50, 20, 70, 40, 25, 60, 15])
        batch_np = next(packed_lm_batches(docs, seq_len=64, batch_size=2))
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        opt = optax.adam(1e-3)
        with use_mesh(mesh):
            state, shardings = create_sharded_state(
                model, opt, mesh, rules, jax.random.key(0), batch
            )
            step = make_train_step(model, mesh, rules, shardings)
            batch = jax.device_put(batch, data_sharding(mesh, rules))
            _, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestCostModel:
    def test_pair_flops_hand_layout(self):
        from dlrover_tpu.telemetry import costmodel

        seg = np.zeros((1, 8), np.int32)
        seg[0, :3] = 1
        seg[0, 3:8] = 2
        summary = costmodel.packed_attention_summary(
            seg, num_heads=2, head_dim=4, num_layers=3
        )
        # Σᵢ sᵢ² = 9 + 25 = 34 vs dense 64; formula 4·pairs·h·d·L/2·3.
        assert summary["attn_flops_packed"] == 4 * 34 * 2 * 4 * 3 * 0.5 * 3
        assert summary["attn_flops_dense"] == 4 * 64 * 2 * 4 * 3 * 0.5 * 3
        np.testing.assert_allclose(summary["reduction"], 64 / 34)
        assert summary["docs"] == 2 and summary["real_tokens"] == 8
        assert summary["packing_efficiency"] == 1.0

    def test_segment_histogram_and_lengths(self):
        seg = np.array([[1, 1, 2, 2, 2, 0], [1, 1, 1, 1, 2, 2]], np.int32)
        assert segment_histogram(seg) == {2: 2, 3: 1, 4: 1}
        assert segment_lengths(seg) == [[2, 3], [4, 2]]

    def test_profiler_packed_prediction(self, monkeypatch):
        from dlrover_tpu.telemetry import profiling

        emitted = []
        monkeypatch.setattr(
            profiling.tevents, "emit",
            lambda kind, **kw: emitted.append((kind, kw)),
        )
        prof = profiling.StepPhaseProfiler(emit_interval=1)
        prof.set_packed_prediction(1000.0, dense_tps=600.0)
        prof.begin_step()
        prof.end_step(0)
        (kind, kw), = [e for e in emitted if e[0] == "step_phase"]
        assert kw["packed_pred_tok_s"] == 1000.0
        assert kw["dense_pred_tok_s"] == 600.0
        assert kw["packed_prediction"] == "costmodel"
        # None turns the annotation off.
        prof.set_packed_prediction(None)
        prof.begin_step()
        prof.end_step(1)
        assert "packed_pred_tok_s" not in emitted[-1][1]


@pytest.mark.slow
class TestTrainerPacking:
    def test_pack_sequences_end_to_end(self, tmp_path):
        """Document stream -> packer -> Trainer with pack_sequences: the
        loop trains and the packed cost-model prediction installs."""
        import optax

        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArguments

        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        model = LlamaModel(cfg)
        rng = np.random.RandomState(0)

        def doc_stream():
            for _ in range(60):
                n = int(rng.randint(10, 60))
                yield rng.randint(1, cfg.vocab_size, size=(n,)).astype(
                    np.int32
                )

        args = TrainingArguments(
            max_steps=3,
            pack_sequences=64,
            pack_batch_size=4,
        )
        trainer = Trainer(
            model=model,
            args=args,
            optimizer=optax.adam(1e-3),
            train_batches=doc_stream(),
        )
        state = trainer.train()
        assert state is not None
