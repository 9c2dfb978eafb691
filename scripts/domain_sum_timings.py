"""Isolated timings of the in-domain sum's forms (ops/interpod.py
in_domain_sums; state/dims.py domain_sum) on whatever device JAX finds:

    chiprun -- python3 scripts/domain_sum_timings.py [--S 72] [--N 5120]

Each form sums per-node [rows, N] tables over every node's topology domain;
all must read equal to the scatter form, element for element, on the data
made here (a key absent on some nodes, invalid nodes, a hostname key with
D = N, sums past 2^16, signed weights). Prints one JSON object; also written
to chiprun_out/domain_sum_timings.json. PERF.md section 6 (PR 42) holds the
readings that chose the form the program keeps.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.ops import interpod


def _data(S: int, N: int, K: int, seed: int):
    rng = np.random.default_rng(seed)
    dom = np.stack([rng.integers(0, 16, N), rng.integers(0, 320, N),
                    np.arange(N), rng.integers(-1, 3, N)], 1)[:, :K]
    nodes = SimpleNamespace(domain=jnp.asarray(dom, jnp.int32),
                            valid=jnp.asarray(rng.random(N) > 0.02))
    keys = jnp.asarray(rng.integers(-1, K, S), jnp.int32)
    cnt = jnp.asarray(rng.integers(0, 400, (S, N)), jnp.int32)
    hold = jnp.asarray(rng.integers(0, 3, (S, N)), jnp.int32)
    wsym = jnp.asarray(rng.integers(-300, 300, (S, N)), jnp.float32)
    return nodes, keys, cnt, hold, wsym


def _time(fn, *args, reps: int = 20) -> float:
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return round(statistics.median(out) * 1e3, 4)


def _lhs(digits, keys, K, dtype):
    own = keys[:, None] == jnp.arange(K)[None, :]
    return jnp.where(own[None, :, :, None], digits[:, :, None, :], 0) \
        .astype(dtype)


def product_int8(rows, keys, same8):
    """Four 7-bit digits (the top one signed) in int8, i32 accumulation."""
    v = rows.astype(jnp.int32)
    digits = jnp.stack([v & 127, (v >> 7) & 127, (v >> 14) & 127, v >> 21])
    out = jax.lax.dot_general(
        _lhs(digits, keys, same8.shape[0], jnp.int8), same8,
        (((2, 3), (0, 1)), ((), ())), preferred_element_type=jnp.int32)
    return ((out[3] << 21) + (out[2] << 14) + (out[1] << 7)
            + out[0]).astype(rows.dtype)


def product_highest(rows, keys, same):
    """f32 rows against the matrix in f32, Precision.HIGHEST."""
    own = keys[:, None] == jnp.arange(same.shape[0])[None, :]
    lhs = jnp.where(own[:, :, None], rows.astype(jnp.float32)[:, None, :], 0)
    out = jax.lax.dot_general(
        lhs, same.astype(jnp.float32), (((1, 2), (0, 1)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    return out.astype(rows.dtype)


def _digits3(v):
    return jnp.stack([v & 255, (v >> 8) & 255, v >> 16])


def _join3(out):
    out = out.astype(jnp.int32)
    return (out[2] << 16) + (out[1] << 8) + out[0]


def onehot_pair(rows, keys, onehot):
    """rows @ OH_k into domain space [A, D], then @ OH_k.T back to nodes:
    two products, the digits split again between them."""
    K = onehot.shape[0]
    dims = (((2, 3), (0, 1)), ((), ()))
    seg = _join3(jax.lax.dot_general(
        _lhs(_digits3(rows.astype(jnp.int32)), keys, K, jnp.bfloat16),
        onehot, dims, preferred_element_type=jnp.float32))       # [A, D]
    back = jax.lax.dot_general(
        _lhs(_digits3(seg), keys, K, jnp.bfloat16),
        onehot.transpose(0, 2, 1), dims,
        preferred_element_type=jnp.float32)
    return _join3(back).astype(rows.dtype)


def scatter_trailing(cnt, hold, wsym, keys, nodes, D):
    """ISSUE 42's fallback: ONE scatter and ONE gather, the three tables on
    a trailing axis."""
    dom, has_key = interpod.domain_of_term(nodes, keys)
    idx = jnp.where(has_key, dom, D)
    vals = jnp.stack([cnt, hold, wsym.astype(jnp.int32)], -1)    # [S, N, 3]
    S = cnt.shape[0]
    seg = jnp.zeros((S, D + 1, 3), jnp.int32).at[
        jnp.arange(S)[:, None], idx].add(vals)
    out = jnp.take_along_axis(seg, idx[:, :, None], axis=1)
    return jnp.where(has_key[:, :, None], out, 0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--S", type=int, default=72)
    ap.add_argument("--N", type=int, default=5120)
    ap.add_argument("--K", type=int, default=4)
    ap.add_argument("--seed", type=int, default=42)
    a = ap.parse_args()
    S, N, K, D = a.S, a.N, a.K, a.N
    nodes, keys, cnt, hold, wsym = _data(S, N, K, a.seed)
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "S": S, "N": N, "K": K, "D": D, "ms": {}, "equal": {}}
    ms, equal = res["ms"], res["equal"]

    build = jax.jit(lambda d, v: interpod.same_domain(
        SimpleNamespace(domain=d, valid=v)))
    same = build(nodes.domain, nodes.valid)
    ms["same_domain_build"] = _time(build, nodes.domain, nodes.valid)
    same8 = same.astype(jnp.int8)
    dom_t = jnp.where(nodes.valid[:, None], nodes.domain, -1).T   # [K, N]
    onehot = (dom_t[:, :, None] == jnp.arange(D)[None, None, :]) \
        .astype(jnp.bfloat16)                                      # [K, N, D]

    def sums(same=None):
        return jax.jit(lambda r, k: interpod.in_domain_sums(
            r, k, nodes, D, same))

    stacked = jnp.concatenate([cnt, hold, wsym.astype(jnp.int32)])
    keys3 = jnp.tile(keys, 3)
    want = sums()(stacked, keys3)

    def three(f):
        return jax.jit(lambda c, h, w: (f(c, keys), f(h, keys), f(w, keys)))

    scatter = lambda r, k: interpod.in_domain_sums(r, k, nodes, D)
    product = lambda r, k: interpod.in_domain_sums(r, k, nodes, D, same)
    ms["scatter_three_tables"] = _time(three(scatter), cnt, hold, wsym)
    ms["scatter_trailing_axis"] = _time(
        jax.jit(lambda c, h, w: scatter_trailing(c, h, w, keys, nodes, D)),
        cnt, hold, wsym)
    ms["product_three_tables"] = _time(three(product), cnt, hold, wsym)
    forms = {
        "product_bf16x3_stacked": sums(same),
        "product_int8x4_stacked": jax.jit(
            lambda r, k: product_int8(r, k, same8)),
        "product_f32_highest_stacked": jax.jit(
            lambda r, k: product_highest(r, k, same)),
        "onehot_pair_stacked": jax.jit(
            lambda r, k: onehot_pair(r, k, onehot)),
    }
    for name, fn in forms.items():
        ms[name] = _time(fn, stacked, keys3)
        equal[name] = bool(jnp.array_equal(fn(stacked, keys3), want))
    got = three(product)(cnt, hold, wsym)
    ref = three(scatter)(cnt, hold, wsym)
    equal["product_three_tables"] = all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(got, ref))
    # the rule's arithmetic: either form against the rows it sums
    for rows in (1, 2, 8, 16, 64, 3 * S, 16 * S):
        r = jnp.tile(stacked, (-(-rows // stacked.shape[0]), 1))[:rows]
        k = jnp.tile(keys3, -(-rows // keys3.shape[0]))[:rows]
        ms[f"scatter_rows_{rows}"] = _time(sums(), r, k, reps=10)
        ms[f"product_rows_{rows}"] = _time(sums(same), r, k, reps=10)
        equal[f"rows_{rows}"] = bool(
            jnp.array_equal(sums()(r, k), sums(same)(r, k)))
    stats = dev.memory_stats() or {}
    res["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/domain_sum_timings.json", "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    return 0 if all(equal.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
