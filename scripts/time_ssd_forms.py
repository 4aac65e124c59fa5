#!/usr/bin/env python3
"""The chunked state-space dual kernel (``ops/ssd.py``) ALONE on the chip at the
seventh judge's shapes, checked and timed form by form (ISSUE 49).

    chiprun -- python scripts/time_ssd_forms.py            # 2-3 chip-minutes
    JAX_PLATFORMS=cpu python scripts/time_ssd_forms.py --tiny   # a rehearsal, no times worth keeping

One layer's scan at [3, 8192], 32 heads of 128 on 2 groups of 256 states, bf16,
``lens`` (7525, 8192, 4000), under the PUBLISHED long-memory initialisation (A =
-(1..32), steps log-uniform in [0.001, 0.1]): the benchmark's seeded weights
give a state that halves every token, under which the cell's check cannot see a
wrong carry between chunks (PERF.md, question 23), and tier-1 holds the carry
in interpret mode only.  So the compiled kernel is compared HERE with the plain
recurrence in float32 on the same device: the outputs at the live positions and
the state after ``lens - 1``, root mean square of the difference over that of
the recurrence's.  Then each form (heads a grid step, positions a chunk) is
timed on the host's clock around ``block_until_ready``, the least of five calls
(one dispatch of a jitted kernel costs the host about 0.55 ms: a form under 2
ms reads that much too long).  One JSON line a form on stdout and in
``chiprun_out/pr49/ssd_forms.jsonl``.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    tiny = "--tiny" in sys.argv
    import jax
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.ops import ssd

    if tiny:
        b, s, heads, p, groups, n, lens = 2, 96, 6, 8, 2, 16, (75, 96)
        forms = [(3, 16), (6, 16), (3, 32)]
    else:
        b, s, heads, p, groups, n, lens = 3, 8192, 32, 128, 2, 256, (7525, 8192, 4000)
        forms = [(16, 128), (8, 128), (4, 128), (16, 256), (8, 256)]
    dtype = jnp.float32 if tiny else jnp.bfloat16
    rng = np.random.default_rng(49)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    a = -np.arange(1, heads + 1, dtype=np.float32)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=heads)).astype(np.float32)
    dt = jax.nn.softplus(jnp.asarray(0.3 * normal(b, s, heads) + step + np.log(-np.expm1(-step))))
    args = (
        jnp.asarray(normal(b, s, heads * p)).astype(dtype), dt, jnp.asarray(a),
        jnp.asarray(normal(b, s, groups * n)).astype(dtype),
        jnp.asarray(normal(b, s, groups * n)).astype(dtype),
        jnp.asarray(1.0 + 0.1 * normal(heads)),
    )
    lens = jnp.asarray(lens, jnp.int32)
    f32 = tuple(x.astype(jnp.float32) for x in args)
    want_y, want_state = jax.jit(lambda *xs: ssd.ssd_recurrent(*xs, lens, groups=groups))(*f32)
    live = (jnp.arange(s)[None, :] < lens[:, None])[..., None]
    rms = lambda x: float(jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))))  # noqa: E731
    device = jax.devices()[0]
    out = os.path.join("chiprun_out", "pr49")
    os.makedirs(out, exist_ok=True)
    lines = []
    for per_step, chunk in forms:
        call = lambda: ssd.ssd_chunked(  # noqa: E731
            *args, lens, groups=groups, chunk=chunk, heads_per_step=per_step
        )
        line = {"heads_per_step": per_step, "chunk": chunk, "device": device.device_kind,
                "platform": device.platform, "dtype": str(jnp.dtype(dtype)), "shape": [b, s, heads, p, groups, n]}
        try:
            y, state = jax.block_until_ready(call())
            line["y_rms_error_over_rms"] = rms(jnp.where(live, y.astype(jnp.float32) - want_y, 0)) / rms(jnp.where(live, want_y, 0))
            line["state_rms_error_over_rms"] = rms(state - want_state) / rms(want_state)
            line["state_rms"] = rms(want_state)
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(call())
                times.append((time.perf_counter() - t0) * 1e3)
            line["ms_min_of_5"] = min(times)
        except Exception as e:  # a form Mosaic refuses is a line, not the end
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        lines.append(line)
    with open(os.path.join(out, "ssd_forms.jsonl"), "w", encoding="utf-8") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)
    bad = [l for l in lines[:1] if "error" in l or l["y_rms_error_over_rms"] > 0.02 or l["state_rms_error_over_rms"] > 0.02]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
