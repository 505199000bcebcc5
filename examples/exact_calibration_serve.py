"""Serving example: generate from a reduced model with the KV cache, and
calibrate int8 activation scales with EXACT quantiles (the paper's
reproducibility argument applied to quantized serving — the scale is
bit-identical across runs and cluster sizes).

Run:  PYTHONPATH=src python examples/exact_calibration_serve.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.serve import (calibrate_int8_scale, calibrate_int8_scales,
                                generate)
from repro.models import model
from repro.launch.chip import enable_compile_cache

enable_compile_cache()

cfg = get_config("h2o-danube-1.8b").reduced()
params = model.init_params(cfg, jax.random.PRNGKey(0))

# --- batched generation (prefill + decode, sliding-window KV ring) ----------
prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 24), 0, cfg.vocab)
toks = generate(cfg, params, prompts, gen_len=12)
print("generated:", np.asarray(toks)[:2])

# --- exact-quantile int8 calibration ----------------------------------------
# collect activations from a calibration batch, then set the scale at the
# exact p99.9 of |activation| — GK Select, not an approximation.  The odd
# size exercises the +inf-sentinel pad + rank-addressed path (zero-padding
# would corrupt the distribution).
acts = (jax.random.normal(jax.random.PRNGKey(2), (65521,)) * 0.25)
scale = calibrate_int8_scale(acts, q=0.999)
oracle = np.sort(np.abs(np.asarray(acts)))[int(np.ceil(0.999 * acts.size)) - 1]
print(f"int8 scale (exact p99.9) = {float(scale):.6f}  oracle={oracle:.6f}")
assert float(scale) == oracle
q8 = jnp.clip(jnp.round(acts / scale * 127), -127, 127).astype(jnp.int8)
rec = q8.astype(jnp.float32) * scale / 127
inside = jnp.abs(acts) <= scale
err = jnp.abs(rec - acts)[inside].max()
print(f"dequant max err (within scale): {float(err):.6f} <= {float(scale)/127:.6f}")

# --- per-channel scales: ONE batched multi-quantile job ---------------------
# C channels calibrated by a single vmapped GK Select dispatch instead of C
# separate exact_quantile jobs (the Spark one-job-per-quantile regression)
ch_acts = jax.random.normal(jax.random.PRNGKey(3), (8191, 6)) * \
    jnp.linspace(0.1, 0.6, 6)
scales = calibrate_int8_scales(ch_acts, axis=-1, q=0.999)
kc = int(np.ceil(0.999 * ch_acts.shape[0]))
ch_oracle = np.sort(np.abs(np.asarray(ch_acts)), axis=0)[kc - 1, :]
print("per-channel scales:", np.asarray(scales).round(4))
assert np.array_equal(np.asarray(scales), ch_oracle)

# --- streaming calibration: running sketch across decode steps --------------
# Instead of capturing an activation history and re-sketching it for every
# scale query, a StreamingCalibrator folds each decode step's |logits| into
# a persistent SketchState; scale queries then run GK Select WARM — the
# sketch phase (the full sort) never happens at query time (DESIGN.md §6).
from repro.core import reset_sketch_sorts, sketch_sorts
from repro.launch import StreamingCalibrator

cal = StreamingCalibrator(q=0.999)
toks2 = generate(cfg, params, prompts, gen_len=12, calibrator=cal)
reset_sketch_sorts()
warm_scale = float(cal.scale("logits"))
assert sketch_sorts() == 0           # warm query: no sketch-phase sort
print(f"streaming calibration over {cal.observed('logits')} |logit| samples: "
      f"exact p99.9 scale = {warm_scale:.6f} "
      f"(approx O(s): {float(cal.approx_scale('logits')):.6f})")
