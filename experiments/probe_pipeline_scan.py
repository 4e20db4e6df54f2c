"""Software-pipelined generate→dot scan vs the production serialized scan.

The production ingest scan (``ops/devicegen.py:_fused_update``) generates
block k on the VPU, materializes it through an ``optimization_barrier``, and
feeds the MXU dot — strictly serialized within each scan step. DESIGN.md §7
measured the dot at ~180 Tmac/s isolated while end-to-end ingest runs at
~55% of that, so up to ~1.4–1.8× would be available IF the VPU generation of
block k could overlap the MXU dot of block k−1.

This probe restructures the scan to carry X: step k generates X_k and dots
X_{k−1} (no data dependence between the two inside one step), with the first
block generated ahead of the scan and the last block's dot issued after it.
Bit-identical to the serial program by construction (parity-checked below,
including the row/kept counters).

Run on the real producer chain at the whole-genome bench config
(N=2504, B=16384, K=32, spacing 73, seed 42) with QUEUED timing: CHAIN
dispatch groups back to back, ONE terminal fetch of a scalar that depends on
the full chain (per-call timing adds a host round trip per call).

Result (v5e, 2026-07-31, medians over 4 rounds of 40-dispatch chains; the
serial program reproduces the whole-genome bench rate in this harness):

    serial     median 41.5 ms/dispatch  (12.6M sites/s)
    pipelined  median 48.2 ms/dispatch  (10.9M sites/s)   +16% SLOWER

NEGATIVE: XLA:TPU executes HLOs in sequence — removing the data dependence
between generation and dot inside the loop body does not make the scheduler
co-issue them; compute overlap on TPU happens inside ONE fusion, and a dot
cannot host the generation chain as a sibling output (that is exactly the
per-tile-recompute fusion the barrier exists to prevent). The carried
(B, N) int8 X adds a 41 MB loop-carry round-trip through HBM per step with
no offsetting win. The production scan stays serialized.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from spark_examples_tpu.ops.devicegen import (
    _fused_update,
    generate_has_variation,
    site_thresholds_on_device,
    _c64,
)
from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

N = 2504
B = 16384
K = 32
SPACING = 73
# 40-dispatch chains: shorter chains carry ~150 ms of fixed queue overhead
# (~15 ms/dispatch at CHAIN=10) and under-report sustained throughput; at 40
# the serial program reproduces the whole-genome bench rate (~13.5M sites/s).
CHAIN = 40
ROUNDS = 4

source = SyntheticGenomicsSource(num_samples=N, seed=42, variant_spacing=SPACING)
VS = "bench-1kg"
update_args = dict(
    vs_keys=(int(source.genotype_stream_key(VS)),),
    pops_bytes=np.asarray(source.populations, dtype=np.int32).tobytes(),
    site_key=int(source.site_key),
    spacing=SPACING,
    ref_block_fraction=source.ref_block_fraction,
    min_af_micro=None,
    block_size=B,
    blocks_per_dispatch=K,
    operand_name="int8",
    accum_name="int32",
    n_pops=source.n_pops,
    set_sizes=None,
)

serial = _fused_update(**update_args)


def build_pipelined():
    """The same program with X software-pipelined through the scan carry."""
    n_pops = update_args["n_pops"]
    ref_frac = update_args["ref_block_fraction"]

    with jax.enable_x64(True):
        # Constants INSIDE x64 or the uint64 keys canonicalize to uint32
        # (exactly how _fused_update builds them).
        vs_keys_arr = jnp.asarray(
            np.array(
                [k & (2**64 - 1) for k in update_args["vs_keys"]],
                dtype=np.uint64,
            )
        )
        pops_arr = jnp.asarray(
            np.frombuffer(update_args["pops_bytes"], dtype=np.int32)
        )
        site_key_arr = _c64(update_args["site_key"])

        @jax.jit
        def update(G, rows_count, kept_count, grid_offset, n_valid):
            block_idx = jnp.arange(K * B, dtype=jnp.int64).reshape(K, B)

            def gen_block(idx):
                index = grid_offset + idx
                positions = index * SPACING
                valid = idx < n_valid
                T = site_thresholds_on_device(
                    site_key_arr, positions, valid, n_pops, ref_frac, None
                )
                kept_inc = jnp.sum(jnp.any(T > 0, axis=1)).astype(jnp.int64)
                hv = generate_has_variation(
                    positions, T, vs_keys_arr, pops_arr, None
                )
                rows_inc = jnp.sum(
                    jnp.any(hv.reshape(hv.shape[0], 1, -1), axis=2), axis=0
                ).astype(jnp.int64)
                X = lax.optimization_barrier(hv.astype(jnp.int8))
                return X, rows_inc, kept_inc

            X0, r0, k0 = gen_block(block_idx[0])

            def body(carry, idx):
                G, rows_count, kept_count, Xp = carry
                Xn, r_inc, k_inc = gen_block(idx)
                G = G + jnp.einsum(
                    "bn,bm->nm", Xp, Xp, preferred_element_type=jnp.int32
                )
                return (G, rows_count + r_inc, kept_count + k_inc, Xn), None

            (G, rows_count, kept_count, Xl), _ = lax.scan(
                body, (G, rows_count + r0, kept_count + k0, X0), block_idx[1:]
            )
            G = G + jnp.einsum(
                "bn,bm->nm", Xl, Xl, preferred_element_type=jnp.int32
            )
            return G, rows_count, kept_count

    return update


pipelined = build_pipelined()


def fresh_state():
    with jax.enable_x64(True):
        return (
            jnp.zeros((N, N), jnp.int32),
            jnp.zeros((1,), jnp.int64),
            jnp.zeros((), jnp.int64),
        )


def run_chain(fn, n_calls, offset0=0):
    G, rows, kept = fresh_state()
    with jax.enable_x64(True):
        for i in range(n_calls):
            G, rows, kept = fn(
                G,
                rows,
                kept,
                jnp.asarray(np.int64(offset0 + i * K * B)),
                jnp.asarray(np.int64(K * B)),
            )
            if i == 0:
                # Production syncs once after the first dispatch
                # (ops/devicegen.py:poke); mirrored here.
                _ = np.asarray(kept)
    return G, rows, kept


# Parity first: bit-identical Gramian and counters over 2 dispatch groups.
Gs, rs, ks = run_chain(serial, 2)
Gp, rp, kp = run_chain(pipelined, 2)
assert np.array_equal(np.asarray(Gs), np.asarray(Gp)), "Gramian mismatch"
assert np.array_equal(np.asarray(rs), np.asarray(rp)), "row-count mismatch"
assert int(ks) == int(kp), "kept-count mismatch"
print(f"parity OK (G sum {int(np.asarray(Gs, dtype=np.int64).sum())})", flush=True)

times = {"serial": [], "pipelined": []}
for rnd in range(ROUNDS):
    for name, fn in (("serial", serial), ("pipelined", pipelined)):
        t0 = time.perf_counter()
        G, rows, kept = run_chain(fn, CHAIN, offset0=rnd * 10_000_000)
        # Terminal fetch depends on the full chain.
        _ = int(np.asarray(G[0, 0])) + int(kept)
        times[name].append((time.perf_counter() - t0) / CHAIN)

for name, ts in times.items():
    ts = sorted(ts)
    med = ts[len(ts) // 2]
    print(
        f"{name:10s} median {med*1e3:7.1f} ms/dispatch  "
        f"min {ts[0]*1e3:7.1f}  max {ts[-1]*1e3:7.1f}",
        flush=True,
    )
