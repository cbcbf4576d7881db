"""End-to-end training driver: train a ~10M-param Qwen3-family model for a
few hundred steps on the synthetic pipeline, with checkpoint + kill/resume.

Run: PYTHONPATH=src python examples/train_lm.py [--steps 300]
(At full scale the same loop runs via `python -m repro.launch.train
--arch qwen3_0_6b --steps ...` on a pod.)
"""

import argparse
import shutil
import tempfile

import jax

from repro.configs import get_reduced_config
from repro.data.pipeline import DataConfig, DataIterator
from repro.distributed.checkpoint import CheckpointManager
from repro.models.model_zoo import build_model
from repro.training import optimizer as opt_mod
from repro.training.train_loop import TrainConfig, TrainLoop, init_state
from repro.compile_cache import use_compile_cache

use_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
args = ap.parse_args()

cfg = get_reduced_config("qwen3_0_6b").replace(
    n_layers=4, d_model=128, n_heads=8, n_kv_heads=4, d_ff=512, head_dim=16,
    vocab=2048,
)
model = build_model(cfg)
n_params = sum(x.size for x in jax.tree.leaves(model.init(jax.random.PRNGKey(0))))
print(f"model: {n_params/1e6:.1f}M params ({cfg.n_layers}L d{cfg.d_model})")

tcfg = TrainConfig(
    opt=opt_mod.OptimizerConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps)
)
workdir = tempfile.mkdtemp(prefix="pulse_train_")
ckpt = CheckpointManager(workdir, async_save=True)
data = DataIterator(DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8))

state = init_state(model, tcfg, jax.random.PRNGKey(0))
loop = TrainLoop(model, tcfg, data, ckpt_manager=ckpt, ckpt_every=100)

half = args.steps // 2
state, log1 = loop.run(state, 0, half)
print(f"[phase 1] step {half}: loss {log1[-1]['loss']:.4f}")
ckpt.save(state, half, extra=data.state_dict(), block=True)

# simulate a node failure: throw everything away, restore, continue
print("[failure] killing training state; restoring from checkpoint...")
del state
state2 = init_state(model, tcfg, jax.random.PRNGKey(99))  # junk init
state2, extra, step0 = ckpt.restore(state2)
data2 = DataIterator(DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8))
data2.load_state_dict(extra)
loop2 = TrainLoop(model, tcfg, data2, ckpt_manager=ckpt, ckpt_every=100)
state2, log2 = loop2.run(state2, step0, args.steps - step0)
print(f"[phase 2] resumed at {step0}, finished step {args.steps - 1}: "
      f"loss {log2[-1]['loss']:.4f}")
first = log1[0]["loss"]
last = log2[-1]["loss"]
print(f"loss {first:.3f} -> {last:.3f} ({'OK' if last < first - 0.5 else 'WARN'})")
ckpt.wait()
shutil.rmtree(workdir, ignore_errors=True)
