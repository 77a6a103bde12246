"""Kernel-level benchmark: reference-impl wall time on CPU (correctness
path) + the TPU roofline characteristics of each Pallas kernel at
production-relevant shapes (arithmetic intensity -> bound regime on v5e:
ridge = 197e12 / 819e9 ~ 241 FLOP/byte).

Emits CSV: kernel,shape,ref_ms_cpu,flops,bytes,intensity,v5e_bound

``smoke()`` is the CI part: a Lookup over a hash_join table against a
searchsorted oracle, and interpret-vs-reference equality sweeps for
radix_groupby and segment_sum — the Pallas kernel BODY validated on CPU — plus the full
intensity CSV written to ``KERNELS_<tag>.csv`` for upload next to the BENCH
json.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention_ref
from repro.core.backend.jax_dims import DimLookup, Plan
from repro.kernels.hash_join import hash_build
from repro.kernels.mamba_scan import mamba_scan_ref
from repro.kernels.radix_groupby import radix_groupby
from repro.kernels.segment_sum import segment_sum, segment_sum_ref

RIDGE = 197e12 / 819e9


def _row_lookup(ht):
    """A jitted Lookup returning each key's build row (-1 where absent)
    over ``hash_build``'s table ``ht``: its slot-addressed column of row
    numbers is ``slot_idx``.  The ``DimLookup`` is made from its leaves, so
    no upload counts as a dimension table's."""
    si = jnp.asarray(ht["slot_idx"])
    arrays = {"cols": {"row": si}}
    if ht["base"] is None:
        arrays.update(slot_keys=tuple(jnp.asarray(k)
                                      for k in ht["slot_keys"]),
                      slot_idx=si)
    look = DimLookup.tree_unflatten(
        Plan(ht["base"], ht["table_size"], ht["max_probes"], None,
             (("row", "row"),), -1, None), (arrays,))
    return lambda p: _lookup_jit(look, p)["row"]


_lookup_jit = jax.jit(lambda look, p: look(p))


def _time(fn, *args):
    # one warmup call (compile + first run), then one timed call — the
    # result is evaluated ONCE per call (a tuple-check must not re-invoke fn)
    r = fn(*args)
    (r[0] if isinstance(r, tuple) else r).block_until_ready()
    t0 = time.perf_counter()
    r = fn(*args)
    (r[0] if isinstance(r, tuple) else r).block_until_ready()
    return (time.perf_counter() - t0) * 1e3


def run() -> list:
    out = ["kernels.kernel,shape,ref_ms_cpu,flops,bytes,intensity,v5e_bound"]
    rng = np.random.default_rng(0)

    # flash attention: one mixtral prefill block per device
    B, S, Kh, G, hd = 1, 2048, 1, 4, 128
    q = jnp.asarray(rng.normal(size=(B, S, Kh, G, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Kh, hd)), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    ms = _time(f, q, k, v)
    flops = 4 * B * S * S * Kh * G * hd / 2        # causal half
    byts = (q.size + 2 * k.size + q.size) * 4
    inten = flops / byts
    out.append(f"kernels.flash_attention,B{B}xS{S}xh{Kh*G}xd{hd},"
               f"{ms:.1f},{flops:.2e},{byts:.2e},{inten:.0f},"
               f"{'compute' if inten > RIDGE else 'memory'}")

    # mamba scan: one falcon-mamba layer chunk per device
    Bt, T, d, N = 1, 2048, 512, 16
    delta = jnp.asarray(np.abs(rng.normal(size=(Bt, T, d))).clip(.01, 1),
                        jnp.float32)
    x = jnp.asarray(rng.normal(size=(Bt, T, d)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(Bt, T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(Bt, T, N)), jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(d, N))) - .05, jnp.float32)
    h0 = jnp.zeros((Bt, d, N), jnp.float32)
    f = jax.jit(mamba_scan_ref)
    ms = _time(f, delta, x, Bm, C, A, h0)
    flops = Bt * T * d * N * 9                     # exp+3mul fma per (c,n)
    byts_fused = (delta.size + x.size + Bm.size + C.size
                  + Bt * T * d) * 4                # fused kernel traffic
    byts_naive = byts_fused + 2 * Bt * T * d * N * 4 * 2  # dA/dBx in HBM
    out.append(f"kernels.mamba_scan,B{Bt}xT{T}xd{d}xN{N},"
               f"{ms:.1f},{flops:.2e},{byts_fused:.2e},"
               f"{flops/byts_fused:.1f},memory")
    out.append(f"kernels.mamba_scan_unfused_traffic_ratio,,,,"
               f"{byts_naive/byts_fused:.1f}x,,")

    # segment sum: the paper's groupby (Fig-11 component 9)
    Nr, Cc, Gg = 1 << 20, 2, 512
    seg = jnp.asarray(rng.integers(0, Gg, Nr).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(Nr, Cc)), jnp.float32)
    f = jax.jit(lambda s, v: segment_sum_ref(s, v, Gg))
    ms = _time(f, seg, vals)
    flops = 2.0 * Nr * Gg * Cc                     # one-hot matmul form
    byts = (Nr * Cc + Nr + Gg * Cc) * 4
    out.append(f"kernels.segment_sum,N{Nr}xC{Cc}xG{Gg},"
               f"{ms:.1f},{flops:.2e},{byts:.2e},{flops/byts:.0f},"
               f"{'compute' if flops/byts > RIDGE else 'memory'}")

    # hash-join probe: the Lookup component at SSB dimension scale, on an
    # fmix32 table (sparse keys), returning one column
    Dd, Np_ = 1 << 15, 1 << 20
    keys = np.sort(rng.choice(1 << 22, size=Dd, replace=False)).astype(np.int64)
    ht = hash_build((keys,))
    probes = jnp.asarray(rng.integers(0, 1 << 22, Np_).astype(np.int64))
    ms = _time(_row_lookup(ht), probes)
    mp = ht["max_probes"]
    flops = 1.0 * Np_ * (6 + 4 * mp)     # fmix32 + per-step cmp/mask chain
    # probe keys in, two gathers a pass, one of the column, the column out
    byts = (Np_ * (3 + 2 * mp) + ht["table_size"] * 3) * 4
    out.append(f"kernels.hash_join,D{Dd}xN{Np_}xp{mp},"
               f"{ms:.1f},{flops:.2e},{byts:.2e},{flops/byts:.1f},memory")

    # radix groupby: dense-id grouped reduce (replaces sort+segment_sum)
    Nr2, Cc2, Gg2 = 1 << 20, 2, 4096
    ids = jnp.asarray(rng.integers(0, Gg2, Nr2).astype(np.int32))
    vals2 = jnp.asarray(rng.normal(size=(Nr2, Cc2)), jnp.float32)
    ms = _time(lambda i, v: radix_groupby(i, v, Gg2, impl="reference"),
               ids, vals2)
    parts = -(-Gg2 // 256)
    flops = 2.0 * Nr2 * 256 * (Cc2 + 1) * parts    # per-partition one-hot
    byts = (parts * Nr2 * (Cc2 + 2) + Gg2 * (Cc2 + 1)) * 4
    out.append(f"kernels.radix_groupby,N{Nr2}xC{Cc2}xG{Gg2},"
               f"{ms:.1f},{flops:.2e},{byts:.2e},{flops/byts:.0f},"
               f"{'compute' if flops/byts > RIDGE else 'memory'}")
    return out


def smoke(data=None):
    """CI part: a Lookup vs a searchsorted oracle, Pallas kernel-body
    (interpret) vs pure-jnp reference equality for the reduce kernels, then
    the intensity CSV written to ``KERNELS_<tag>.csv`` (uploaded with the
    BENCH json artifacts)."""
    rng = np.random.default_rng(7)
    failures = 0

    # hash-join: sorted unique keys, hit and miss probes
    try:
        keys = np.sort(rng.choice(5_000, size=700, replace=False)
                       ).astype(np.int64)
        ht = hash_build((keys,))
        probes = jnp.asarray(rng.integers(0, 6_000, 3_000).astype(np.int64))
        rows = np.asarray(_row_lookup(ht)(probes))
        # vs the searchsorted oracle (found rows index the leftmost match)
        pv = np.asarray(probes)
        ss = np.clip(np.searchsorted(keys, pv), 0, len(keys) - 1)
        hit = keys[ss] == pv
        assert np.array_equal(rows, np.where(hit, ss, -1))
        print(f"smoke.kernels.hash_join,ok,probes={len(pv)},"
              f"hits={int(hit.sum())},max_probes={ht['max_probes']}")
    except Exception:
        import traceback
        traceback.print_exc()
        failures += 1
        print("smoke.kernels.hash_join,FAIL")

    # radix groupby: interpret vs reference, padding rows included
    try:
        ids = rng.integers(-1, 600, size=20_000).astype(np.int32)
        vals = rng.normal(size=(20_000, 3)).astype(np.float32)
        s_r, c_r = radix_groupby(jnp.asarray(ids), jnp.asarray(vals), 600,
                                 impl="reference")
        s_i, c_i = radix_groupby(jnp.asarray(ids), jnp.asarray(vals), 600,
                                 impl="interpret")
        np.testing.assert_allclose(np.asarray(s_r), np.asarray(s_i),
                                   rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.asarray(c_r), np.asarray(c_i))
        print(f"smoke.kernels.radix_groupby,ok,groups=600,"
              f"rows={int(np.asarray(c_r).sum())}")
    except Exception:
        import traceback
        traceback.print_exc()
        failures += 1
        print("smoke.kernels.radix_groupby,FAIL")

    # segment sum: interpret vs reference (regression guard for the shared
    # one-hot matmul pattern all three reduce kernels use)
    try:
        seg = jnp.asarray(rng.integers(0, 64, 8_192).astype(np.int32))
        v = jnp.asarray(rng.normal(size=(8_192, 2)), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(segment_sum(seg, v, 64, impl="interpret")),
            np.asarray(segment_sum(seg, v, 64, impl="reference")),
            rtol=1e-5, atol=1e-5)
        print("smoke.kernels.segment_sum,ok")
    except Exception:
        import traceback
        traceback.print_exc()
        failures += 1
        print("smoke.kernels.segment_sum,FAIL")

    # the intensity CSV artifact (small shapes run fine on CPU)
    try:
        tag = os.environ.get("BENCH_TAG", "").strip() or "local"
        path = f"KERNELS_{tag}.csv"
        with open(path, "w") as f:
            f.write("\n".join(run()) + "\n")
        print(f"# wrote {path}")
    except Exception:
        import traceback
        traceback.print_exc()
        failures += 1
        print("smoke.kernels.csv,FAIL")
    return failures


if __name__ == "__main__":
    print("\n".join(run()))
