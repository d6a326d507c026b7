"""PPO on a toy reward with the N-model RLHF engine.

Reference analog: the atorch RLHF engine examples.  Four models
(actor/critic/reference/reward — here reward is a rule) drive the full
loop: KV-cached rollout generation, GAE advantages, clipped PPO updates
with a KL penalty against the frozen reference policy.

The toy reward favors even tokens, a dense signal a random policy can
climb immediately — after a few PPO steps the actor's rollouts contain
measurably more even tokens, which the script asserts.

    python examples/rlhf/train_ppo.py

``--external`` runs the hybrid-engine topology for real: rollouts come
from a SEPARATE generation-server process (the vLLM-backend analog) over
the framework RPC, with content-hashed weight pushes between PPO
iterations and stale-version refusal.  For per-model sharding strategies
(actor fsdp×tp, critic fsdp, ref replicated...) see
``dlrover_tpu/rl/model_engine.py``.
"""

import argparse
import os
import sys

sys.path.insert(
    0,
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
)

import numpy as np


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true", help="tiny CI run")
    p.add_argument("--ppo-steps", type=int, default=8)
    p.add_argument("--gen-len", type=int, default=16)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--external", action="store_true",
                   help="rollouts from a real external generation-server "
                   "process (weight push + version checks)")
    args = p.parse_args(argv)
    if args.smoke:
        args.ppo_steps, args.gen_len, args.batch = 2, 8, 4

    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.rl.engine import RLHFConfig, RLHFEngine
    from dlrover_tpu.rl.models import CriticModel

    cfg = LlamaConfig.tiny(dtype=jnp.float32, num_layers=1)

    def even_token_reward(tokens, mask):
        """Sequence reward: fraction of generated tokens that are even."""
        even = (tokens % 2 == 0).astype(np.float32) * mask
        return even.sum(-1) / np.maximum(mask.sum(-1), 1.0)

    backend = None
    server_proc = None
    if args.external:
        import subprocess
        import tempfile
        import time as _time

        from dlrover_tpu.rl.generation_server import (
            ExternalGenerationBackend,
        )

        ready = os.path.join(tempfile.mkdtemp(prefix="genserver_"), "ready")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # the server honors it in-process
        server_proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.rl.generation_server",
             "--port", "0",
             "--model-factory", "dlrover_tpu.rl.models:tiny_actor_factory",
             "--ready-file", ready],
            env=env,
        )
        deadline = _time.time() + 90
        while _time.time() < deadline and not os.path.exists(ready):
            assert server_proc.poll() is None, "generation server died"
            _time.sleep(0.2)
        with open(ready) as f:
            backend = ExternalGenerationBackend(f"127.0.0.1:{f.read()}")
        assert backend.ready(30)
        print("external generation server up")

    engine = RLHFEngine(
        LlamaModel(cfg),
        CriticModel(cfg),
        even_token_reward,
        RLHFConfig(
            gen_len=args.gen_len,
            minibatch_size=4,
            ppo_epochs=1,
            kl_coef=0.05,
            generation_backend="external" if args.external else "auto",
        ),
        sample_prompt=jnp.zeros((1, 4), jnp.int32),
        generation_backend=backend,
    )

    prompts = jnp.zeros((args.batch, 4), jnp.int32)
    rewards = []
    for it in range(args.ppo_steps):
        stats = engine.step(prompts)
        rewards.append(stats["mean_score"])
        print(
            f"iter {it}: score={stats['mean_score']:.3f} "
            f"policy_loss={stats.get('policy_loss', float('nan')):.4f} "
            f"entropy={stats.get('entropy', float('nan')):.4f}"
        )

    if backend is not None:
        st = backend.status()
        print(f"server: params v{st.params_version}, "
              f"{st.generated} tokens generated")
        assert st.params_version >= 1
        backend.close()
        server_proc.terminate()
        server_proc.wait(timeout=10)
    print(f"score {rewards[0]:.3f} -> {rewards[-1]:.3f}")
    if not args.smoke:
        half = len(rewards) // 2
        assert np.mean(rewards[half:]) > np.mean(rewards[:half]), (
            "policy did not improve"
        )
    return rewards[-1]


if __name__ == "__main__":
    main()
