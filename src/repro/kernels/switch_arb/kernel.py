"""Pallas TPU kernel: fused per-switch crossbar arbitration.

Hardware adaptation (docs/DESIGN.md): the simulator's arbitration stage is
a batch of tiny independent problems — one per switch — with no cross-switch
data flow.  The kernel tiles ``block_n`` switches per grid step and keeps a
whole switch's requester block ``[R, P]`` resident in VMEM, fusing

* routing-score evaluation (``occ + penalty * deroute + tie``, masked),
* per-requester port selection (VPU argmin over ports), and
* segmented output arbitration (per-port max-priority reduction over the
  requester axis)

into one pass, so the ``[NR, P]`` score/priority intermediates never hit
HBM.  The score axis is padded to the 128-lane boundary and the requester
axis to the 8-sublane boundary (f32 tile = (8, 128)); padded lanes carry
``mask = 0`` -> score ``BIG`` and padded rows carry ``route = 0``, so they
can never win a grant and the unpadded results are bitwise those of
``ref.switch_arbitrate_ref``.

``vc_prearb`` (stage 1 of the sub-round) is likewise tiled per switch.  It
cannot fuse into the arbitration kernel: between the two stages the engine
gathers the selected head packets and their attributes from state arrays
(data-dependent addresses spanning the whole pool), which is exactly the
irregular access Pallas blocks are not shaped for — see DESIGN.md.  Its
``[P, V]`` trailing block is left unpadded (V is 4).  Mosaic accepts it,
but the TPU tiles pad V to 128 lanes, so the compiled kernel at 104,976
endpoints holds ~0.5 GB of padded temporaries; a 128-lane ``[P * V]``
layout would remove them.

All randomness is drawn by the caller (``jax.random`` on the host stream)
and passed in as tensors, which is what makes kernel, oracle, and inline
XLA engine bitwise interchangeable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# python float, not a jnp scalar: kernel bodies must not capture traced
# constants, and weak-typed 1e9 promotes to the same f32 the engine uses
BIG = 1e9


def _pad_to(x, mults, fill):
    """Pad trailing dims of ``x`` up to multiples of ``mults`` (leading dims
    untouched when the corresponding mult is 1)."""
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if not any(hi for _, hi in pads):
        return x
    return jnp.pad(x, pads, constant_values=fill)


# ---------------------------------------------------------------------- #
# stage 1: VC pre-arbitration
# ---------------------------------------------------------------------- #
def _prearb_kernel(qlen_ref, rand_ref, sel_ref, has_ref):
    prio = jnp.where(qlen_ref[...] > 0, rand_ref[...], -1.0)
    sel_ref[...] = jnp.argmax(prio, axis=-1).astype(jnp.int32)
    has_ref[...] = (jnp.max(prio, axis=-1) >= 0.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def vc_prearb(qlen, rand, block_n: int = 8, interpret: bool = False):
    """Per-switch-tiled VC pre-arbitration.  [N, P, V] -> ([N, P], [N, P])."""
    n, p, v = qlen.shape
    qlen = _pad_to(qlen, (block_n, 1, 1), 0)
    rand = _pad_to(rand, (block_n, 1, 1), 0.0)
    np_ = qlen.shape[0]
    grid = (np_ // block_n,)
    sel, has = pl.pallas_call(
        _prearb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, p, v), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_n, p, v), lambda i: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n, p), lambda i: (i, 0)),
            pl.BlockSpec((block_n, p), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((np_, p), jnp.int32),
            jax.ShapeDtypeStruct((np_, p), jnp.int32),
        ),
        interpret=interpret,
        name="vc_prearb",
    )(qlen, rand)
    return sel[:n], has[:n]


# ---------------------------------------------------------------------- #
# stages 2+3: fused score evaluation + segmented output arbitration
# ---------------------------------------------------------------------- #
def _arb_kernel(occ_ref, der_ref, mask_ref, tie_ref, route_ref, rnd_ref,
                lo_ref, port_ref, win_ref, seg_ref, *, penalty: float):
    score = (occ_ref[...].astype(jnp.float32)
             + penalty * der_ref[...].astype(jnp.float32) + tie_ref[...])
    score = jnp.where(mask_ref[...] > 0, score, BIG)
    port = jnp.argmin(score, axis=-1).astype(jnp.int32)
    can = (route_ref[...] > 0) & (jnp.min(score, axis=-1) < BIG)
    prio = jnp.where(can, (rnd_ref[...] << 23) | lo_ref[...], -1)
    p_ids = jax.lax.broadcasted_iota(jnp.int32, score.shape, 2)
    # Mosaic cannot add a trailing unit dim to a bool vector, so ``can``
    # crosses into 3-D as int32
    can3 = can.astype(jnp.int32)[:, :, None] > 0
    onehot = (port[:, :, None] == p_ids) & can3                 # [BN,R,P]
    seg = jnp.max(jnp.where(onehot, prio[:, :, None], -1), axis=1)
    seg_at = jnp.sum(jnp.where(onehot, seg[:, None, :], 0), axis=-1)
    port_ref[...] = port
    win_ref[...] = (can & (seg_at == prio)).astype(jnp.int32)
    seg_ref[...] = seg


@functools.partial(jax.jit, static_argnames=("penalty", "block_n",
                                             "interpret"))
def switch_arbitrate(occ, deroute, mask, tie, route, rnd, lo, *,
                     penalty: float, block_n: int = 8,
                     interpret: bool = False):
    """Fused arbitration over the dense per-switch layout.

    Shapes/dtypes as in :func:`repro.kernels.switch_arb.ref
    .switch_arbitrate_ref`; returns ``(port, win)`` int32 [N, R] plus the
    per-output-port winning priority ``seg`` int32 [N, P].
    """
    n, r, p = occ.shape
    m3, m2 = (block_n, 8, 128), (block_n, 8)
    occ = _pad_to(occ, m3, 0)
    deroute = _pad_to(deroute, m3, 0)
    mask = _pad_to(mask, m3, 0)
    tie = _pad_to(tie, m3, 0.0)
    route = _pad_to(route, m2, 0)
    rnd = _pad_to(rnd, m2, 0)
    lo = _pad_to(lo, m2, 0)
    np_, rp, pp = occ.shape
    grid = (np_ // block_n,)
    spec3 = pl.BlockSpec((block_n, rp, pp), lambda i: (i, 0, 0))
    spec2 = pl.BlockSpec((block_n, rp), lambda i: (i, 0))
    spec_seg = pl.BlockSpec((block_n, pp), lambda i: (i, 0))
    port, win, seg = pl.pallas_call(
        functools.partial(_arb_kernel, penalty=penalty),
        grid=grid,
        in_specs=[spec3, spec3, spec3, spec3, spec2, spec2, spec2],
        out_specs=(spec2, spec2, spec_seg),
        out_shape=(
            jax.ShapeDtypeStruct((np_, rp), jnp.int32),
            jax.ShapeDtypeStruct((np_, rp), jnp.int32),
            jax.ShapeDtypeStruct((np_, pp), jnp.int32),
        ),
        interpret=interpret,
        name="switch_arbitrate",
    )(occ, deroute, mask, tie, route, rnd, lo)
    return port[:n, :r], win[:n, :r], seg[:n, :p]
