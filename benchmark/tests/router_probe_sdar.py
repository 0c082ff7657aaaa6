"""Where the ``sdar`` cell's router gradient loses precision (PERF.md 2a, PR 37): one
expert layer at the cell's widths (d 2,048, f 768, 128 router outputs, 16 held, top 8)
over T random tokens, the gradient of sum(out * g) to the router's weights against
float32 HIGHEST, in the reference's arithmetics and in the program's layer.  Not part of
a benchmark run; a few minutes on the CPU:

    JAX_PLATFORMS=cpu python3 benchmark/tests/router_probe_sdar.py [T]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import reference_sdar as ref  # noqa: E402
from fedml_tpu.models import expert_lm  # noqa: E402

T, d, f, R, H, k = int(sys.argv[1]) if len(sys.argv) > 1 else 512, 2048, 768, 128, 16, 8
key = jax.random.PRNGKey(0)
ks = jax.random.split(key, 8)
h = jax.random.normal(ks[0], (T, d), jnp.float32)
w_r = jax.random.normal(ks[1], (d, R), jnp.float32) / np.sqrt(d)
moe = {"e_gate": jax.random.normal(ks[2], (H, d, f)) / np.sqrt(d),
       "e_up": jax.random.normal(ks[3], (H, d, f)) / np.sqrt(d),
       "e_down": jax.random.normal(ks[4], (H, f, d)) / np.sqrt(f)}
g = jax.random.normal(ks[5], (T, d), jnp.float32)
model = {"experts_held": [0, H], "num_experts_per_tok": k}
bf = jnp.bfloat16


def ref_layer(w_r, precision, bf16_combine=False):
    w = {"router": w_r, "moe": moe}
    if not bf16_combine:
        return ref.expert_layer(h[None], w, model, precision)[0]
    chosen, weights = ref.router(h[None], w_r, model)
    out = jnp.zeros((1, T, d), jnp.float32)
    for e in range(H):
        wt = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        y = ref.swiglu(h[None], moe["e_gate"][e], moe["e_up"][e], moe["e_down"][e], precision)
        out = out + (wt.astype(bf)[..., None] * y.astype(bf)).astype(jnp.float32)
    return out[0]


def program_layer(w_r, h_router_f32=False):
    hb = h.astype(bf)
    src = h if h_router_f32 else hb.astype(jnp.float32)
    logits = jnp.matmul(src, w_r, precision=jax.lax.Precision.HIGHEST)
    chosen, weights = expert_lm.route(logits, None, k, softmax_chosen=True)
    out, _ = expert_lm.grouped_experts(hb, chosen, weights, (0, H), moe["e_gate"].astype(bf),
                                       moe["e_up"].astype(bf), moe["e_down"].astype(bf), R)
    return out.astype(jnp.float32)


def grad(fn):
    return jax.jit(jax.grad(lambda w: jnp.sum(fn(w) * g)))(w_r)


truth = grad(lambda w: ref_layer(w, "highest"))
for name, fn in [("reference bfloat16", lambda w: ref_layer(w, "bfloat16")),
                 ("reference bfloat16, weight x expert in bf16", lambda w: ref_layer(w, "bfloat16", True)),
                 ("reference float8", lambda w: ref_layer(w, "float8")),
                 ("program (bf16 residual, bf16 combine)", program_layer),
                 ("program, router on float32 input", lambda w: program_layer(w, True))]:
    got = grad(fn)
    print(f"{name:48s} direction {float(jnp.linalg.norm(got - truth) / jnp.linalg.norm(truth)):.4f}"
          f"  norm gap {float(abs(jnp.linalg.norm(got) - jnp.linalg.norm(truth)) / jnp.linalg.norm(truth)):.5f}",
          flush=True)
