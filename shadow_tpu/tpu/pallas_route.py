"""Fused Pallas routing placement: per-destination-tile bucketed append
in ONE pass, shared by both Pallas kernels.

The XLA routing path (plane.window_step section 5) computes the bucketed
arrival order (`plane._routing_rank`) and then lands the payload columns
with one stacked [6, N, CI] gather. This kernel does the placement per
tile of destination rows: each row's bucket segment of the
arrival-sorted stream is appended after the row's existing entries in
one masked select — rank computation (bucket offset - current
occupancy) and scatter-append collapse into a windowed load plus a
compare mask, with no per-column placement dispatches.

The arrival order itself still comes from the XLA diet sort (sorting
across the host axis is what XLA's comparator networks are for; under
a sharded mesh that sort IS the all-to-all), and so do the sorted
payload streams, which that sort carries. They are stacked as one
[8, ~B] array that stays in HBM (`pl.ANY`): per destination row the
kernel DMAs the 128-lane-aligned span covering that row's segment into
a small ring of VMEM windows (`_DMA_LAG` rows in flight) and rotates
the segment to lane 0. The
stream is never resident in VMEM whole — at N = 32,768 hosts and the
config's default caps it is 32 MiB per column.

`plane_kernel: pallas` feeds it the XLA path's row-local seq order;
`plane_kernel: pallas_fused` feeds it kernel A's (`pallas_pipeline`).
FIFO worlds only (the flag already requires `rr_enabled=False`);
`window_step` refuses the combination with threaded faults or guards at
trace time. Off-TPU the kernel runs in Pallas interpret mode
(`pallas_interpret`), parity-tested against the XLA path in
tests/test_plane_routing.py; tests/test_chip_compile.py compiles it for
a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_interpret
from .pallas_egress import _pick_tile, _rotate_like_numpy
from .plane import I32_MAX, _routing_rank

# destination rows whose window DMAs are in flight at once
_DMA_LAG = 8
_LANES = 128


def _window_width(ci: int) -> int:
    """Lanes per DMA'd window: HBM slices must start and end on a
    128-lane tile, so a CI-wide window at an unaligned offset is read
    as the aligned span that covers it."""
    return -(-ci // _LANES) * _LANES + _LANES


def _place_kernel(lo_ref, nv_ref, take_ref, streams,
                  b_src, b_seq, b_sock, b_bytes, b_del, b_valid,
                  o_src, o_seq, o_sock, o_bytes, o_del, o_valid,
                  win, sems):
    T, CI = b_src.shape
    W = win.shape[-1]
    outs = (o_src, o_seq, o_sock, o_bytes, o_del)

    def window(r):
        # row r's bucket segment starts at stream slot lo[r] + CI (the
        # stream is CI-left-padded): fetch the aligned span covering it
        start = pl.multiple_of((lo_ref[0, 0, r] + CI) // _LANES * _LANES,
                               _LANES)
        slot = r % _DMA_LAG
        return pltpu.make_async_copy(streams.at[:, pl.ds(start, W)],
                                     win.at[slot], sems.at[slot])

    for r in range(min(_DMA_LAG, T)):
        window(r).start()
    lanes = jax.lax.broadcasted_iota(jnp.int32, win.shape[1:], 1)
    like_np = _rotate_like_numpy(lanes)

    def land(r, carry):
        window(r).wait()
        # rotate the segment's first item to lane 0: window column c IS
        # the item for row slot c
        off = (lo_ref[0, 0, r] + CI) % _LANES
        w = win[r % _DMA_LAG]
        seg = jnp.where(like_np, pltpu.roll(w, (W - off) % W, 1),
                        pltpu.roll(w, off, 1))[:, :CI]
        for k, o_ref in enumerate(outs):
            o_ref[pl.ds(r, 1), :] = seg[k:k + 1, :]

        @pl.when(r + _DMA_LAG < T)
        def _():
            window(r + _DMA_LAG).start()

        return carry

    jax.lax.fori_loop(0, T, land, 0)

    # append mask: slots [nv, nv + take) of each destination row receive
    # the bucket's segment; every other slot keeps its base value
    nv = nv_ref[...]
    ccol = jax.lax.broadcasted_iota(jnp.int32, (T, CI), 1)
    mask = (ccol >= nv) & (ccol < nv + take_ref[...])
    for b_ref, o_ref in zip((b_src, b_seq, b_sock, b_bytes, b_del), outs):
        o_ref[...] = jnp.where(mask, o_ref[...], b_ref[...])
    o_valid[...] = jnp.where(mask, 1, b_valid[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _place_call(nv, lo, take, streams, b_src, b_seq, b_sock, b_bytes,
                b_del, b_valid, interpret: bool):
    N, CI = b_src.shape
    T = _pick_tile(N, CI)
    row_spec = pl.BlockSpec((T, CI), lambda i: (i, 0))
    col_spec = pl.BlockSpec((T, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _place_kernel,
        grid=(N // T,),
        in_specs=[pl.BlockSpec((1, 1, T), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),  # lo
                  col_spec, col_spec,  # nv, take
                  pl.BlockSpec(memory_space=pl.ANY)]  # streams, in HBM
        + [row_spec] * 6,
        out_specs=[row_spec] * 6,
        out_shape=[jax.ShapeDtypeStruct((N, CI), jnp.int32)] * 6,
        scratch_shapes=[
            pltpu.VMEM((_DMA_LAG,) + streams.shape[:1]
                       + (_window_width(CI),), jnp.int32),
            pltpu.SemaphoreType.DMA((_DMA_LAG,))],
        interpret=interpret,
    )(lo.reshape(N // T, 1, T), nv.reshape(N, 1), take.reshape(N, 1),
      streams, b_src, b_seq, b_sock, b_bytes, b_del, b_valid)


def route_place(sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
                in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
                in_valid_c, n_valid_in, row_perm=None):
    """The fused routing stage (+ the XLA exchange): land the routed
    arrivals into the destination tiles, bitwise equal to the XLA path's
    `_routing_rank` + `_routing_place` composition over the compacted
    ingress columns. `row_perm` is kernel A's fused seq-order inverse,
    or None to compute it in XLA (`plane_kernel: pallas`). Returns the
    merged ingress columns + per-host overflow, like
    `plane._route_scatter`."""
    CI = in_src_c.shape[1]
    # the irreducible cross-host exchange: ONE diet flat sort over the
    # (bucket, deliver, slot) keys, carrying the payload, + binary-
    # searched bucket bounds
    streams, offsets, take_n, overflow = _routing_rank(
        sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel, n_valid_in,
        CI, row_perm=row_perm)
    lo = offsets - n_valid_in

    # the arrival-sorted payload streams, stacked to fill the 8-sublane
    # HBM tile and padded CI on the left and one window on the right (to
    # a whole lane tile), so every aligned window is in bounds; padding
    # is never selected: masked lanes only cover the bucket's own segment
    B = streams[0].shape[0]
    right = _window_width(CI)
    right += -(CI + B + right) % _LANES
    streams = jnp.pad(jnp.stack(streams),
                      ((0, 8 - len(streams)), (CI, right)))

    (in_src_m, in_seq_m, in_sock_m, in_bytes_m, in_deliver_m,
     in_valid_m) = _place_call(
        n_valid_in, lo, take_n, streams,
        in_src_c, in_seq_c, in_sock_c, in_bytes_c,
        jnp.where(in_valid_c, in_deliver_c, I32_MAX),
        in_valid_c.astype(jnp.int32), pallas_interpret())
    return (in_src_m, in_seq_m, in_sock_m, in_bytes_m, in_deliver_m,
            in_valid_m != 0, overflow)
