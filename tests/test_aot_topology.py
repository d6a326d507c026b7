"""AOT compile-for-topology regression (round-5, VERDICT ask #6).

``jax.experimental.topologies`` + ``jit(...).lower(...).compile()`` runs
the real XLA TPU compiler against a device-less slice topology, which
upgrades the 8-virtual-CPU-device dryrun ("the sharded program executes
somewhere") to "the real program compiles for real slice hardware".
This keeps a tiny always-on regression; the flagship programs (llama-7B
fsdp x tp on v5e-16 and the int8 DCN Local-SGD sync on 2 slices) are
compiled by scripts/aot_slice_compile.py.

No TPU involved: the topology client never dials a device.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _topo(name, **kw):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=name, **kw)
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this env
        pytest.skip(f"TPU compile-only client unavailable: {e}")


class TestAotTopology:
    def test_sharded_grad_compiles_for_v5e_2x2(self):
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        topo = _topo("v5e:2x2")
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tp"))

        def loss(w, x):
            return jnp.tanh(x @ w).sum()

        wsh = NamedSharding(mesh, P("fsdp", "tp"))
        xsh = NamedSharding(mesh, P(None, "fsdp"))
        w = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16, sharding=wsh)
        x = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16, sharding=xsh)
        compiled = jax.jit(
            jax.grad(loss), in_shardings=(wsh, xsh), out_shardings=wsh
        ).lower(w, x).compile()
        txt = compiled.as_text()
        # fsdp-sharded contraction => cross-chip reduction in the HLO.
        assert "all-reduce" in txt or "reduce-scatter" in txt
        assert compiled.cost_analysis().get("flops", 0) > 0

    def test_multislice_topology_exposes_slice_indices(self):
        topo = _topo("v5e:2x2", num_slices=2)
        slices = {getattr(d, "slice_index", 0) for d in topo.devices}
        assert len(topo.devices) == 8
        assert slices == {0, 1}
