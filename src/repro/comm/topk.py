"""TopKReducer: magnitude sparsification of displacements, optionally
composed with int8 quantization of the survivors (the int8_topk scheme).

Per learner and per leaf the largest-|.| k_frac fraction of displacement
entries is kept and the rest zeroed; with error feedback the zeroed mass
returns as residual next round, which is what makes aggressive k_frac
(default 10%) safe. Wire accounting per kept value: a 4-byte index plus
the value itself (4 bytes dense, 1 byte when int8-quantized) — so
int8_topk at k_frac=0.1 ships ~1/8 of dense.

Masked-then-quantized values stay exactly zero through the stochastic
rounding (floor(0/s + u) = 0 for u < 1), so the sparsity pattern survives
the wire.

On the packed flat meta-plane (repro.pack, DESIGN.md §9) the whole
displacement arrives as one leaf, so selection becomes whole-model-vector
top-k — the form the communication-efficient analyses state it in —
rather than per-leaf budgets: a layer with uniformly small displacements
may ship nothing while a hot layer ships more than k_frac of its own
entries (error feedback returns the skipped mass either way). Padding
slots are exact zeros and are never selected (the ``ab > 0`` guard), but
they do inflate ``k = round(k_frac * n)`` by the pad fraction —
negligible on the real configs, conservative (ships more) on tiny ones.
Packed-vs-per-leaf top-k parity is pinned at the trajectory level in
tests/test_pack.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.comm.quant import SCALE_BYTES, VALUE_BYTES, QuantReducer
from repro.comm.reducer import CompressedReducer
from repro.kernels import ops as kops

INDEX_BYTES = 4.0


class TopKReducer(CompressedReducer):
    def __init__(self, k_frac: float = 0.1, quant_dtype: str | None = None,
                 chunk_rows: int = 64, use_pallas: bool = False, seed: int = 0):
        assert 0.0 < k_frac <= 1.0, k_frac
        self.k_frac = k_frac
        self.quant = (
            QuantReducer(dtype=quant_dtype, chunk_rows=chunk_rows,
                         use_pallas=use_pallas, seed=seed)
            if quant_dtype else None
        )
        self.name = f"{quant_dtype}_topk" if quant_dtype else "topk"

    def _compress(self, delta, step):
        leaves, treedef = jax.tree_util.tree_flatten(delta)
        out, wire = [], 0.0
        for i, leaf in enumerate(leaves):
            L = leaf.shape[0]
            flat = leaf.reshape(L, -1)
            n = flat.shape[1]
            k = max(1, int(round(self.k_frac * n)))
            ab = jnp.abs(flat)
            thresh = lax.top_k(ab, k)[0][:, -1:]
            # `ab > 0` guards the all-ties-at-zero case: a mostly-zero leaf
            # has thresh == 0 and `>= thresh` alone would keep everything,
            # breaking the <= k-per-learner wire accounting
            c = jnp.where((ab >= thresh) & (ab > 0), flat, 0.0).reshape(leaf.shape)
            vb = VALUE_BYTES[self.quant.dtype] if self.quant else 4.0
            wire += L * k * (vb + INDEX_BYTES)
            if self.quant:
                c, nchunks = kops.quant_dequant(
                    c, self.quant._leaf_key(i, step), dtype=self.quant.dtype,
                    block=self.quant.chunk_rows,
                    use_pallas=self.quant.use_pallas,
                )
                wire += nchunks * SCALE_BYTES
            out.append(c)
        return jax.tree_util.tree_unflatten(treedef, out), wire
