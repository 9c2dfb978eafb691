"""Isolated timings of the in-domain sum's forms (ops/interpod.py
in_domain_sums; state/dims.py domain_sum) on whatever device JAX finds:

    chiprun -- python3 scripts/domain_sum_timings.py [--S 72] [--N 5120]

Each form sums per-node [rows, N] tables over every node's topology domain;
all must read equal to the scatter form, element for element, on the data
made here (a key absent on some nodes, invalid nodes, a hostname key with
D = N, sums past 2^16, signed weights). Prints one JSON object; also written
to chiprun_out/domain_sum_timings_N<N>.json. PERF.md section 6 (PR 42) holds the
readings that chose the form the program keeps.

Since PR 43 also topology spread's aggregate (ops/topospread.py
spread_counts, eligible_in_domain), under `spread_ms` / `spread_equal`: the
parent's scatter-add + gather for SC x TS rows once and twice (it ran in the
Filter row and again for the quota), the product alone and stacked with the
[3 S, N] pass, one class's [TS, N] rows either way, and the eligible-DOMAIN
scatter-max (ELD) against the eligible-node product (ELN); `--skip-forms`
leaves PR 42's part out. PERF.md section 6 (PR 43) holds those readings.
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
    dom = np.stack([rng.integers(0, 16, N), rng.integers(0, min(320, N), N),
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


def eligible_domains_scatter(node_match, tsc_key, nodes, D):
    """The parent's ELD [SC, TS, D+1] (ops/topospread.py before PR 43): a
    scatter-max of SC x TS x N booleans, once a cycle."""
    SC, TS = tsc_key.shape
    dom = nodes.domain[:, jnp.maximum(tsc_key, 0)]               # [N, SC, TS]
    ok = (node_match.T[:, :, None] & (dom >= 0)
          & (tsc_key >= 0)[None, :, :] & nodes.valid[:, None, None])
    return jnp.zeros((SC, TS, D + 1), bool).at[
        jnp.arange(SC)[None, :, None], jnp.arange(TS)[None, None, :],
        jnp.where(ok, dom, D)].max(ok)


def spread_parent(cnt_rows, nm_rows, eld, keys, maxskew, nodes, D,
                  copy="both"):
    """The parent's copies for A = SC x TS rows, each with its own
    eligible-masked scatter-add and the minimum over ELD's domains:
    "filter" gathers the count back (`spread_row`; the soft score's merged
    with it), "quota" gathers the cap from the [D + 1] table
    (`spread_slot`)."""
    dom, has_key = interpod.domain_of_term(nodes, keys)
    seg = interpod.domain_agg(jnp.where(nm_rows, cnt_rows, 0), dom, D)
    idx = jnp.where(dom >= 0, dom, D)
    min_cnt = jnp.min(jnp.where(eld[:, :D], seg[:, :D], _I32_MAX), axis=-1)
    out = (min_cnt,)
    if copy != "quota":
        out += (jnp.where(has_key, jnp.take_along_axis(seg, idx, axis=1), 0),)
    if copy != "filter":
        quota = jnp.clip(maxskew[:, None] + min_cnt[:, None] - seg, 0,
                         _I32_MAX)
        out += (jnp.take_along_axis(quota, idx, axis=1),)
    return out


def spread_new(cnt_rows, nm_rows, eln, keys, maxskew, nodes, D, same):
    """The same three from per-node sums (ops/topospread.py spread_counts
    and ops/waves.py spread_slot's cap)."""
    cnt = interpod.in_domain_sums(jnp.where(nm_rows, cnt_rows, 0), keys,
                                  nodes, D, same)
    min_cnt = jnp.min(jnp.where(eln, cnt, _I32_MAX), axis=-1)
    cap = jnp.clip(maxskew[:, None] + min_cnt[:, None] - cnt, 0, _I32_MAX)
    return min_cnt, cnt, cap


def spread_timings(res, nodes, same, stacked, keys3, SC, TS, N, K, D, seed):
    """`spread_ms` / `spread_equal`: see the module docstring."""
    from kubernetes_tpu.ops import topospread

    rng = np.random.default_rng(seed + 1)
    A = SC * TS
    ms, equal = res.setdefault("spread_ms", {}), \
        res.setdefault("spread_equal", {})
    res["SC"], res["TS"] = SC, TS
    tsc_key = jnp.asarray(rng.integers(0, K, (SC, TS)), jnp.int32)
    keys = tsc_key.reshape(A)
    node_match = jnp.asarray(rng.random((SC, N)) < 0.7) & nodes.valid[None]
    node_match = node_match.at[3].set(False)          # no eligible node
    nm_rows = jnp.repeat(node_match, TS, axis=0)                  # [A, N]
    cnt_a = jnp.asarray(rng.integers(0, 400, (A, N)), jnp.int32)
    cnt_b = jnp.asarray(rng.integers(0, 400, (A, N)), jnp.int32)
    maxskew = jnp.asarray(rng.integers(1, 3, A), jnp.int32)
    classes = SimpleNamespace(tsc_key=tsc_key)

    eld_f = jax.jit(lambda nm: eligible_domains_scatter(nm, tsc_key, nodes, D))
    eln_f = jax.jit(lambda nm: topospread.eligible_in_domain(
        nm, classes, nodes, D, same))
    eln_s = jax.jit(lambda nm: topospread.eligible_in_domain(
        nm, classes, nodes, D))
    # the 0/1 rows need ONE bf16 digit, not three
    eln_1 = jax.jit(lambda nm: jax.lax.dot_general(
        _lhs(jnp.repeat(nm, TS, axis=0)[None].astype(jnp.bfloat16), keys, K,
             jnp.bfloat16), same, (((2, 3), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)[0].reshape(SC, TS, N) > 0)
    ms["eld_scatter_max"] = _time(eld_f, node_match)
    ms["eln_product"] = _time(eln_f, node_match)
    ms["eln_product_one_digit"] = _time(eln_1, node_match)
    ms["eln_scatter_form"] = _time(eln_s, node_match)
    eld = eld_f(node_match).reshape(A, D + 1)
    eln = eln_f(node_match).reshape(A, N)
    dom, has_key = interpod.domain_of_term(nodes, keys)
    equal["eln_is_eld_gathered"] = bool(jnp.array_equal(
        eln, has_key & jnp.take_along_axis(eld, jnp.where(has_key, dom, D), 1)))
    equal["eln_one_digit"] = bool(jnp.array_equal(
        eln_1(node_match).reshape(A, N), eln))
    equal["eln_scatter_form"] = bool(jnp.array_equal(
        eln_s(node_match).reshape(A, N), eln))

    parent = lambda c, copy="both": spread_parent(
        c, nm_rows, eld, keys, maxskew, nodes, D, copy)
    new = lambda c, sm: spread_new(c, nm_rows, eln, keys, maxskew, nodes, D, sm)
    once = jax.jit(parent)
    ms["parent_filter_copy"] = _time(jax.jit(lambda c: parent(c, "filter")),
                                     cnt_a)
    ms["parent_quota_copy"] = _time(jax.jit(lambda c: parent(c, "quota")),
                                    cnt_a)
    # a round ran both, on the same state under different conds: given two
    # states here so that XLA cannot merge the scatter-adds either
    ms["parent_round_both_copies"] = _time(
        jax.jit(lambda a, b: (parent(a, "filter"), parent(b, "quota"))),
        cnt_a, cnt_b)
    ms["scatter_form_shared"] = _time(jax.jit(lambda c: new(c, None)), cnt_a)
    prod = jax.jit(lambda c: new(c, same))
    ms["product_alone"] = _time(prod, cnt_a)
    want = once(cnt_a)
    got = prod(cnt_a)
    # the cap is compared on keyed nodes: a keyless node shares bucket D in
    # the parent's and reads count 0 here, and the Filter row refuses it
    equal["product_vs_parent"] = bool(
        jnp.array_equal(got[0], want[0]) and jnp.array_equal(got[1], want[1])
        and jnp.array_equal(jnp.where(has_key, got[2], 0),
                            jnp.where(has_key, want[2], 0)))
    equal["eligible_rows_exist"] = bool(
        eln.any() and (got[0] < _I32_MAX).any() and (got[0] == _I32_MAX).any())
    equal["scatter_form_vs_product"] = all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(jax.jit(lambda c: new(c, None))(cnt_a), got))
    # with the round's [3 S, N] pass: one product of 3 S + A rows, or two
    rows_a = jnp.where(nm_rows, cnt_a, 0)
    sums = lambda r, k: interpod.in_domain_sums(r, k, nodes, D, same)
    ms["table_product_alone"] = _time(jax.jit(sums), stacked, keys3)
    ms["table_and_spread_two_products"] = _time(
        jax.jit(lambda t, r: (sums(t, keys3), sums(r, keys))), stacked, rows_a)
    ms["table_and_spread_one_product"] = _time(
        jax.jit(lambda t, r: sums(jnp.concatenate([t, r]),
                                  jnp.concatenate([keys3, keys]))),
        stacked, rows_a)
    # one class's own rows (a verb's pod, a what-if lane, a scan step)
    one = slice(5 * TS, 6 * TS)
    p1 = lambda c, sm: spread_new(c, nm_rows[one], eln[one], keys[one],
                                  maxskew[one], nodes, D, sm)
    ms["one_class_parent_filter_copy"] = _time(jax.jit(
        lambda c: spread_parent(c, nm_rows[one], eld[one], keys[one],
                                maxskew[one], nodes, D, "filter")),
        cnt_a[one])
    ms["one_class_product"] = _time(jax.jit(lambda c: p1(c, same)), cnt_a[one])
    ms["one_class_scatter_form"] = _time(jax.jit(lambda c: p1(c, None)),
                                         cnt_a[one])
    # the same class under a vmap of 8: a what-if's lanes (each its own
    # survivors' counts), a verb's pods
    lanes = jnp.stack([cnt_a[one] + i for i in range(8)])
    ms["eight_lanes_parent_filter_copy"] = _time(jax.jit(jax.vmap(
        lambda c: spread_parent(c, nm_rows[one], eld[one], keys[one],
                                maxskew[one], nodes, D, "filter"))), lanes)
    ms["eight_lanes_product"] = _time(
        jax.jit(jax.vmap(lambda c: p1(c, same))), lanes)


_I32_MAX = int(np.iinfo(np.int32).max)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--S", type=int, default=72)
    ap.add_argument("--N", type=int, default=5120)
    ap.add_argument("--K", type=int, default=4)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--SC", type=int, default=64)
    ap.add_argument("--TS", type=int, default=1)
    ap.add_argument("--skip-forms", action="store_true",
                    help="time only the spread aggregate (PR 43's part)")
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
    spread_timings(res, nodes, same, stacked, keys3, a.SC, a.TS, N, K, D,
                   a.seed)
    if a.skip_forms:
        return _finish(res, dev)
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
    return _finish(res, dev)


def _finish(res, dev) -> int:
    stats = dev.memory_stats() or {}
    res["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    os.makedirs("chiprun_out", exist_ok=True)
    name = f"chiprun_out/domain_sum_timings_N{res['N']}.json"
    with open(name, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))
    ok = all(res["equal"].values()) and all(res["spread_equal"].values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
