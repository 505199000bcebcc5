"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps on
CPU with exact-quantile gradient clipping + checkpoint/resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import dataclasses

from repro.configs import get_config
from repro.launch.train import train_loop
from repro.launch.chip import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    # ~100M params: stablelm family, shrunk depth/width but real vocab
    base = get_config("stablelm-1.6b")
    cfg = dataclasses.replace(
        base, n_layers=6, d_model=768, n_heads=12, n_kv_heads=12, d_head=64,
        d_ff=2048, vocab=32000, name="stablelm-100m",
        attn_q_block=128, attn_kv_block=256)
    print(f"config: {cfg.name}  params~{cfg.param_count():,}")

    out = train_loop(cfg, steps=args.steps, global_batch=8, seq_len=256,
                     ckpt_dir=args.ckpt_dir, ckpt_every=50, lr=1e-3,
                     quantile_clip=0.999, log_every=10)
    first, last = out["losses"][0], out["losses"][-1]
    print(f"\nloss {first:.3f} -> {last:.3f} over {out['final_step']} steps "
          f"(p50 {out['loss_p50']:.3f})")
    assert last < first, "loss should decrease"


if __name__ == "__main__":
    main()
