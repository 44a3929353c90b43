"""Pluggable gradient-exchange strategies.

TPU-native rebuild of the reference's exchanger strategy layer
(reference: ``lib/exchanger_strategy.py`` — ``Exch_allreduce`` (host
MPI), ``Exch_copper``/``Exch_cudaaware`` (GPU-direct MPI), ``Exch_asa32``
/ ``Exch_asa16`` (hand-rolled alternating-segmented ring allreduce, fp32
and fp16-compressed), ``Exch_nccl32``/``Exch_nccl16`` (NCCL); SURVEY.md
§2.1, §5.8).

A strategy is a function ``grads -> synced_grads`` executed INSIDE the
compiled SPMD step (under ``shard_map``), where the reference ran Python
MPI calls between Theano calls. All strategies produce the **mean**
gradient across the data axis.

Like the reference's ``BSP_Exchanger``, gradients are packed into one
contiguous buffer before the collective (the paper's "big fused buffer"
optimization) — for ``psum`` XLA would fuse anyway, but for the explicit
ring variants the single buffer is what makes segmentation work.

Strategy names keep the reference's config vocabulary as aliases:
``ar``/``cudaaware``/``nccl32`` -> psum, ``asa32`` -> ring,
``asa16``/``nccl16`` -> ring_bf16 / psum_bf16.

check_vma pin & migration plan
------------------------------
Every shard_map in this framework passes ``check_vma=False``, because
the whole strategy abstraction assumes classic pmap AD semantics: the
transpose of a forward psum is a psum, so each device's backward yields
its LOCAL gradient contribution and the strategy's explicit collective
completes the global mean. Under ``check_vma=True`` (the modern
default) the cotangent of a replicated parameter arrives ALREADY
globally summed — running any strategy here on top of that would
multiply by the axis size. Both behaviors are pinned by a canary
(tests/test_check_vma_canary.py, measured on jax 0.9.0) that fails
loudly if a JAX upgrade changes either side.

Migration (executed when the canary trips, or deliberately): in checked
mode the exchanger degenerates to ``g / axis_size`` with NO collective
for the psum family — a working checked-mode BSP step lives in the
canary file as the prototype. The explicit ring/compressed strategies
do not survive the migration as gradient SYNCS (AD already summed), but
remain useful as weight-exchange collectives (EASGD/GoSGD param
averaging) and would move there. The migration must flip all shard_maps
at once — grep ``check_vma=False``; a mixed tree double-counts.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree

PyTree = Any
Strategy = Callable[[PyTree], PyTree]


def _packed(fn):
    """Wrap a flat-buffer collective into a pytree strategy: pack all
    gradient leaves into one contiguous fp32 vector, run the collective,
    unpack (reference: ``BSP_Exchanger`` pre-concatenated per-param GPU
    buffers into one big comm buffer)."""

    def strategy(grads: PyTree) -> PyTree:
        flat, unravel = ravel_pytree(grads)
        out = fn(flat.astype(jnp.float32))
        return unravel(out.astype(flat.dtype))

    return strategy


# --------------------------------------------------------------------------
# psum family — XLA-native allreduce (≙ Exch_nccl32 / Exch_allreduce /
# Exch_cudaaware: on TPU, one ICI collective replaces all three tiers)
# --------------------------------------------------------------------------


def psum_mean(axis_name: str) -> Strategy:
    def strategy(grads):
        return lax.pmean(grads, axis_name)

    return strategy


def psum_bf16(axis_name: str) -> Strategy:
    """Compressed allreduce: bf16 operands into a single pmean
    (≙ ``Exch_nccl16``; see also EQuARX, PAPERS.md). NOTE: XLA reduces in
    the operand dtype, so accumulation here is bf16 too — cheapest, but at
    large worker counts low-order gradient bits are lost; ``ring_bf16``
    is the bf16-wire / fp32-accumulate variant."""

    def strategy(grads):
        return jax.tree_util.tree_map(
            lambda g: lax.pmean(g.astype(jnp.bfloat16), axis_name).astype(g.dtype),
            grads,
        )

    return strategy


# --------------------------------------------------------------------------
# explicit segmented ring — ≙ Exch_asa32 / Exch_asa16
# --------------------------------------------------------------------------


def _ring_allreduce_flat(
    flat: jax.Array, axis_name: str, n: int, wire: Optional[str] = None
) -> jax.Array:
    """Alternating-segmented ring allreduce on a flat fp32 buffer:
    reduce-scatter (n-1 ppermute steps) + allgather (n-1 steps), the
    algorithm the reference hand-rolled over ``MPI.Sendrecv`` segments
    (reference: ``lib/exchanger_strategy.py`` — ``Exch_asa32``).

    ``wire`` compresses each transferred segment: ``"bf16"`` casts (≙ the
    fp16 compression of ``Exch_asa16``), ``"int8"`` quantizes with a
    per-segment scale through the Pallas kernels in ops/pallas_quant.py
    (EQuARX-style, 4x wire compression); accumulation stays fp32 either
    way. Returns the SUM; caller divides for the mean.
    """
    if n == 1:
        return flat
    L = flat.shape[0]
    seg = -(-L // n)
    if wire == "int8":
        # the quantizer's lane layout needs 128-multiple segments
        seg = -(-seg // 128) * 128
    buf = jnp.zeros((n, seg), flat.dtype).reshape(-1).at[:L].set(flat).reshape(n, seg)
    # mark the carry device-varying so the fori_loop carry types line up
    # under shard_map's varying-manual-axes checking
    buf = lax.pcast(buf, axis_name, to="varying")
    rank = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    if wire not in (None, "bf16", "int8"):
        raise ValueError(f"unknown wire compression {wire!r} (None|bf16|int8)")

    def send(chunk):
        if wire == "int8":
            # the codec layer owns the packed int8 wire format (block-
            # scaled values + scale tail rows); the ring is a consumer
            from theanompi_tpu.parallel.codec import wire_decode, wire_encode

            # ONE packed message per hop (values + scale bytes)
            return wire_decode(lax.ppermute(wire_encode(chunk), axis_name, fwd))
        if wire == "bf16":
            chunk = chunk.astype(jnp.bfloat16)
        out = lax.ppermute(chunk, axis_name, fwd)
        return out.astype(flat.dtype)

    def rs_step(t, b):
        idx_send = jnp.mod(rank - t, n)
        idx_recv = jnp.mod(rank - t - 1, n)
        recv = send(jnp.take(b, idx_send, axis=0))
        return b.at[idx_recv].add(recv)

    buf = lax.fori_loop(0, n - 1, rs_step, buf)
    # node r now owns the fully-reduced segment (r + 1) mod n

    if wire == "int8":
        # Allgather with PACKED forwarding: the owner quantizes its
        # reduced segment ONCE; the int8 bytes then travel every hop
        # UNCHANGED and every device (owner included) decodes the same
        # message. Re-quantizing at each hop is NOT bit-idempotent (the
        # re-derived scale fl(fl(127*s)/127) drifts 1 ulp on ~3% of
        # buffers — found empirically in review), which would leave
        # replicas at different hop distances holding different values
        # and break BSP's replicated-state invariant. Packed forwarding
        # is also cheaper: one quantize total instead of one per hop.
        from theanompi_tpu.parallel.codec import wire_decode, wire_encode

        own = jnp.mod(rank + 1, n)
        packed = wire_encode(jnp.take(buf, own, axis=0))
        buf = buf.at[own].set(wire_decode(packed))

        def ag_step_packed(t, carry):
            b, pk = carry
            pk = lax.ppermute(pk, axis_name, fwd)
            idx_recv = jnp.mod(rank - t, n)
            return b.at[idx_recv].set(wire_decode(pk)), pk

        buf, _ = lax.fori_loop(0, n - 1, ag_step_packed, (buf, packed))
        return buf.reshape(-1)[:L]

    if wire == "bf16":
        # bf16 re-cast IS exact (value already representable), so the
        # plain hop loop keeps replicas identical once the owner's kept
        # segment is cast-aligned with what receivers hold
        own = jnp.mod(rank + 1, n)
        buf = buf.at[own].set(
            jnp.take(buf, own, axis=0).astype(jnp.bfloat16).astype(flat.dtype)
        )

    def ag_step(t, b):
        idx_send = jnp.mod(rank + 1 - t, n)
        idx_recv = jnp.mod(rank - t, n)
        recv = send(jnp.take(b, idx_send, axis=0))
        return b.at[idx_recv].set(recv)

    buf = lax.fori_loop(0, n - 1, ag_step, buf)
    return buf.reshape(-1)[:L]


def ring(axis_name: str, axis_size: int) -> Strategy:
    return _packed(
        lambda flat: _ring_allreduce_flat(flat, axis_name, axis_size) / axis_size
    )


def ring_bf16(axis_name: str, axis_size: int) -> Strategy:
    return _packed(
        lambda flat: _ring_allreduce_flat(flat, axis_name, axis_size, wire="bf16")
        / axis_size
    )


def ring_int8(axis_name: str, axis_size: int) -> Strategy:
    """int8-wire ring: each segment quantized (Pallas kernel, per-segment
    absmax scale) before the hop, dequantized and accumulated in fp32 —
    4x less ICI/DCN traffic than fp32, 2x less than bf16. Quantization
    noise is bounded by amax/254 per hop; suitable for gradient exchange
    (EQuARX, PAPERS.md), not for exact parity checks."""
    return _packed(
        lambda flat: _ring_allreduce_flat(flat, axis_name, axis_size, wire="int8")
        / axis_size
    )


# --------------------------------------------------------------------------
# codec-compressed psum — the codec layer (parallel/codec.py) applied to
# the default in-step gradient allreduce: quantize each device's LOCAL
# grads (error-feedback residual threaded through engine state), mean
# in fp32. The stateful form is the generalization of psum_bf16 /
# ring_int8 that EVERY engine's exchange shares.
# --------------------------------------------------------------------------


def codec_psum_mean(axis_name, codec) -> Strategy:
    """Compressed allreduce ``(grads, ef) -> (mean grads, ef')``; the
    error-feedback residuals arrive STACKED ``[1, ...]`` per device
    (engine-state convention — see codec.compress_stacked). Marked
    ``stateful`` so train.make_train_step threads ``state.ef``."""

    def strategy(grads, ef):
        wire, ef = codec.compress_stacked(grads, ef)
        return lax.pmean(wire, axis_name), ef

    strategy.stateful = True
    return strategy


# --------------------------------------------------------------------------
# hierarchical two-hop exchange — the topology-aware 'hier' strategy
# (GC3-style staged schedule, arXiv:2201.11840; EQuARX's quantize-the-
# starved-hop result, arXiv:2506.17615): in-slice reduce-scatter over
# ICI, cross-slice allreduce over DCN on ONLY the scattered 1/s shards
# (the wire codec applies to this hop alone, where bytes dominate),
# then in-slice all-gather. Codec-off it moves exactly flat psum's
# 2(n-1)/n·N·b total wire, re-split (s-1)/s·N·b + (s-1)/s·N·b on ICI
# and 2(r-1)/r·(N/s)·b on DCN — but the DCN share shrinks by the slice
# width s, which is what keeps scaling efficiency up when a second
# slice joins the mesh (ROADMAP item 4).
# --------------------------------------------------------------------------


def hier_segment(n_elements: int, ici_size: int) -> int:
    """Per-device DCN shard length of the hierarchical exchange: the
    flat gradient buffer padded up to an ``ici_size`` multiple and
    reduce-scattered — ``ceil(N / s)``. The declared two-hop
    TrafficModel (obs/comm.py::bsp_traffic) prices the same geometry,
    which is what makes SPMD101 reconcile byte-exact."""
    return -(-int(n_elements) // max(1, int(ici_size)))


def _check_hier_axes(axis_name, axis_sizes, axis_size=None):
    if isinstance(axis_name, str) or len(tuple(axis_name)) != 2:
        raise ValueError(
            "strategy 'hier' needs a 2-axis (dcn, data) mesh — build it "
            "with make_multislice_mesh (the --slices knob); on a 1-D "
            "mesh there is no slice boundary to schedule around, use "
            "'psum'"
        )
    if not axis_sizes or len(tuple(axis_sizes)) != 2:
        raise ValueError(
            "strategy 'hier' needs axis_sizes=(n_slices, per_slice) in "
            "mesh-axis order (parallel/mesh.py::slice_topology)"
        )
    if axis_size is not None and \
            int(axis_sizes[0]) * int(axis_sizes[1]) != int(axis_size):
        raise ValueError(
            f"hier axis_sizes {tuple(axis_sizes)} do not multiply to the "
            f"mesh size {axis_size}"
        )


def _hier_exchange_flat(flat, dcn_axis, ici_axis, r: int, s: int,
                        dcn_wire=None):
    """One hierarchical allreduce (SUM — caller divides) on a flat fp32
    buffer: reduce-scatter over the in-slice ICI axis (each device ends
    holding the slice-local sum of its 1/s segment), allreduce over the
    cross-slice DCN axis on only that segment (``dcn_wire`` value-space
    compresses this hop alone), all-gather the reduced segments back
    over ICI."""
    L = flat.shape[0]
    seg = hier_segment(L, s)
    if s > 1:
        buf = jnp.zeros((s * seg,), flat.dtype).at[:L].set(flat)
        shard = lax.psum_scatter(buf, ici_axis, scatter_dimension=0,
                                 tiled=True)
    else:
        shard = flat
    if r > 1:
        if dcn_wire is not None:
            shard = dcn_wire(shard)
        shard = lax.psum(shard, dcn_axis)
    if s > 1:
        out = lax.all_gather(shard, ici_axis, tiled=True)
        return out[:L]
    return shard


def hierarchical_sync(axis_names, axis_sizes, codec=None) -> Strategy:
    """The ``hier`` Strategy: ``axis_names = (dcn_axis, ici_axis)`` and
    ``axis_sizes = (n_slices, per_slice)`` in mesh order
    (make_multislice_mesh rows are slices). Codec-off it is a flat pmean
    re-associated slice-first (allclose, not bit-identical — the
    summation tree differs). An active codec compresses ONLY the DCN
    hop: stateless codecs value-space-quantize the in-slice-reduced
    shard before the cross-slice psum; ``:ef`` threads a per-device
    residual on that shard through engine state (stacked ``(1, seg)``
    rows — hier_ef_template), so quantization error is fed back exactly
    where it is introduced."""
    from theanompi_tpu.parallel.codec import get_codec

    dcn_axis, ici_axis = tuple(axis_names)
    r, s = int(axis_sizes[0]), int(axis_sizes[1])
    n = r * s
    codec = get_codec(codec)

    if codec.active and codec.error_feedback:

        def strategy(grads, ef):
            flat, unravel = ravel_pytree(grads)
            fl = flat.astype(jnp.float32)
            L = fl.shape[0]
            seg = hier_segment(L, s)
            if s > 1:
                buf = jnp.zeros((s * seg,), fl.dtype).at[:L].set(fl)
                shard = lax.psum_scatter(buf, ici_axis,
                                         scatter_dimension=0, tiled=True)
            else:
                shard = fl
            if r > 1:
                wire, ef = codec.compress_stacked(shard, ef)
                shard = lax.psum(wire, dcn_axis)
            shard = shard / n
            out = (lax.all_gather(shard, ici_axis, tiled=True)[:L]
                   if s > 1 else shard)
            return unravel(out.astype(flat.dtype)), ef

        strategy.stateful = True
        return strategy

    qdq = codec.qdq if codec.active else None

    def strategy(grads):
        flat, unravel = ravel_pytree(grads)
        out = _hier_exchange_flat(
            flat.astype(jnp.float32), dcn_axis, ici_axis, r, s,
            dcn_wire=qdq,
        ) / n
        return unravel(out.astype(flat.dtype))

    return strategy


def hier_ef_template(params, axis_sizes, bucket_bytes=None):
    """Global error-feedback template for the hier ``:ef`` composition:
    the DCN-shard residual, stacked to one row per device — a single
    ``(n, seg)`` fp32 zeros array whose dim 0 the recipe's ef prefix
    spec shards, so each device holds its own ``(1, seg)`` row (the
    compress_stacked convention). With ``bucket_bytes`` (the bucketed+
    hier+``:ef`` composition) one such array per bucket, ordered like
    assign_buckets, each keyed to that bucket's packed flat segment."""
    r, s = int(axis_sizes[0]), int(axis_sizes[1])
    n = r * s
    leaves = jax.tree_util.tree_leaves(params)

    def _zeros(n_elements):
        return jnp.zeros((n, hier_segment(n_elements, s)), jnp.float32)

    if bucket_bytes is None:
        total = sum(
            int(math.prod(getattr(l, "shape", ()) or ()) or 1)
            for l in leaves
        )
        return _zeros(total)
    return tuple(
        _zeros(sum(
            int(math.prod(getattr(leaves[i], "shape", ()) or ()) or 1)
            for i in idx
        ))
        for idx in assign_buckets(leaves, bucket_bytes)
    )


def _pack_flat(leaves):
    """Concatenate leaves into one flat fp32 buffer (the per-bucket
    packing of the bucketed+hier composition)."""
    flats = [l.astype(jnp.float32).reshape(-1) for l in leaves]
    return flats[0] if len(flats) == 1 else jnp.concatenate(flats)


def _unpack_flat(flat, leaves):
    """Inverse of _pack_flat against the original leaves' shapes/dtypes."""
    out, off = [], 0
    for l in leaves:
        sz = int(math.prod(getattr(l, "shape", ()) or ()) or 1)
        out.append(flat[off:off + sz].reshape(jnp.shape(l)).astype(l.dtype))
        off += sz
    return out


# --------------------------------------------------------------------------
# bucketed overlap-with-backward allreduce — GC3-style collective
# scheduling (PAPERS.md, arXiv:2201.11840): chunk the gradient pytree
# into ~MB-sized buckets and launch each bucket's psum AS SOON AS its
# grads are produced, so the collective overlaps the tail of backward
# instead of serializing after it. The ``--allreduce-buckets`` knob.
# --------------------------------------------------------------------------


def _leaf_wire_bytes(leaf) -> int:
    """fp32 wire bytes of one gradient leaf (grads cross the exchanger
    in fp32 regardless of param dtype — see _packed)."""
    return int(math.prod(getattr(leaf, "shape", ()) or ()) or 1) * 4


def assign_buckets(leaves, bucket_bytes: int) -> list:
    """Group leaf INDICES into contiguous buckets of ~``bucket_bytes``,
    walking leaves in REVERSE flatten order: backward produces grads
    for late-forward params first, so reverse-order buckets fill (and
    their collectives launch) in gradient-production order. Leaf
    granularity — a single leaf over the budget gets its own bucket
    (no intra-leaf chunking); deterministic in the leaf sizes."""
    buckets, cur, cur_b = [], [], 0
    for i in reversed(range(len(leaves))):
        b = _leaf_wire_bytes(leaves[i])
        if cur and cur_b + b > bucket_bytes:
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(i)
        cur_b += b
    if cur:
        buckets.append(cur)
    return buckets


def bucket_overlap_frac(n_buckets: int) -> float:
    """Schedule-level overlap estimate for the attribution model
    (obs/attribution.py): with B buckets launched as their grads are
    produced, all but the LAST bucket's collective can hide under the
    remaining backward compute — the tail bucket is always exposed.
    ``(B-1)/B``; 0 for the single post-backward collective."""
    n = int(n_buckets or 0)
    return (n - 1) / n if n > 1 else 0.0


class BucketedOverlapSync:
    """Bucketed gradient allreduce with overlap-with-backward.

    Mechanism: each bucket's param leaves pass through a
    ``custom_vjp`` identity tag on the FORWARD side; the tag's backward
    applies the bucket's pmean to the cotangents at the exact point the
    backward pass produces them. Reverse-mode order then interleaves
    the B collectives with the remaining backward computation and XLA's
    async collective scheduling can hide all but the tail bucket
    (``train.make_train_step`` detects ``in_backward`` and wraps the
    params inside the differentiated loss instead of transforming grads
    after it). Numerics are IDENTICAL to the single ``psum_mean``:
    pmean is leafwise, so B per-bucket pmeans compute exactly the same
    per-leaf means (bit-identical — tests/test_bucketed.py).

    Codec composition (parallel/codec.py): stateless codecs (``bf16``,
    plain ``int8``) quantize each bucket's LOCAL cotangents value-space
    before the pmean — ``codec_psum_mean`` per bucket. Error feedback
    (``:ef``) is engine STATE the vjp boundary cannot thread (a
    backward rule yields cotangents, not residuals), so the ``:ef``
    path runs post-backward instead: per-bucket ``compress_stacked`` +
    pmean, stateful — bucketed wire scheduling without the structural
    overlap, EF residuals keyed per bucket's leaves. ``in_backward`` /
    ``stateful`` tell the step builder which contract applies.

    Hierarchical composition (``axis_sizes`` set): each bucket's
    cotangents pack into one flat buffer and run the two-hop
    hierarchical exchange instead of a flat pmean — so every bucket's
    DCN hop (the expensive one) overlaps the remaining backward, and a
    codec compresses only that hop. ``:ef`` residuals become one
    ``(1, seg_b)`` shard-row per bucket (hier_ef_template).
    """

    def __init__(self, axis_name, bucket_mb: float = 8.0, codec=None,
                 axis_sizes=None):
        from theanompi_tpu.parallel.codec import get_codec

        if not bucket_mb or bucket_mb <= 0:
            raise ValueError(
                f"--allreduce-buckets needs a positive bucket size in "
                f"MB, got {bucket_mb!r}"
            )
        self.axis_name = axis_name
        self.bucket_mb = float(bucket_mb)
        self.bucket_bytes = max(1, int(bucket_mb * 2 ** 20))
        self.codec = get_codec(codec)
        self.axis_sizes = (tuple(int(x) for x in axis_sizes)
                           if axis_sizes is not None else None)
        self.hier = self.axis_sizes is not None
        if self.hier:
            _check_hier_axes(axis_name, self.axis_sizes)
        self.stateful = self.codec.active and self.codec.error_feedback
        self.in_backward = not self.stateful

    # -- schedule geometry ---------------------------------------------------
    def buckets_for(self, tree) -> list:
        return assign_buckets(jax.tree_util.tree_leaves(tree),
                              self.bucket_bytes)

    def n_buckets(self, tree) -> int:
        return len(self.buckets_for(tree))

    def overlap_frac(self, tree) -> float:
        if not self.in_backward:
            return 0.0  # post-backward :ef path: nothing hides
        return bucket_overlap_frac(self.n_buckets(tree))

    # -- in-backward path (stateless codecs) ---------------------------------
    def _qdq(self, c):
        if not self.codec.active:
            return c
        # value-space wire compression of the LOCAL contribution, fp32
        # accumulation inside the collective — codec_psum_mean's
        # compress path, minus the residual state
        return self.codec.qdq(c.astype(jnp.float32)).astype(c.dtype)

    def _hier_mean(self, leaves):
        """One bucket's hierarchical exchange: pack the leaves into a
        flat fp32 buffer, two-hop mean (codec on the DCN hop only),
        unpack — the bucketed+hier composition's collective."""
        dcn_axis, ici_axis = tuple(self.axis_name)
        r, s = self.axis_sizes
        out = _hier_exchange_flat(
            _pack_flat(leaves), dcn_axis, ici_axis, r, s,
            dcn_wire=self.codec.qdq if self.codec.active else None,
        ) / (r * s)
        return _unpack_flat(out, leaves)

    def _make_tag(self):
        axis = self.axis_name
        qdq = self._qdq
        hier_mean = self._hier_mean if self.hier else None

        @jax.custom_vjp
        def tag(*leaves):
            return leaves

        def fwd(*leaves):
            return leaves, None

        def bwd(_, cts):
            if hier_mean is not None:
                return tuple(hier_mean(list(cts)))
            return tuple(lax.pmean(qdq(c), axis) for c in cts)

        tag.defvjp(fwd, bwd)
        return tag

    def wrap_params(self, params):
        """Tag the param pytree per bucket INSIDE the differentiated
        loss; the cotangents then arrive at each tag's backward already
        grouped, and the bucket's collective posts right there."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        out = list(leaves)
        tag = self._make_tag()
        # tagged LAST bucket first: jax's backward runs the tags' rules
        # in the order the tags were applied, and the collectives must
        # be posted in gradient-PRODUCTION order (output-layer leaves
        # first) — the bsp_bucketed golden pins that schedule
        for idx in reversed(assign_buckets(leaves, self.bucket_bytes)):
            tagged = tag(*[leaves[i] for i in idx])
            for j, i in enumerate(idx):
                out[i] = tagged[j]
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- post-backward path (:ef — and the no-tag fallback) ------------------
    def __call__(self, grads, ef=None):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        buckets = assign_buckets(leaves, self.bucket_bytes)
        if not self.stateful:
            out = list(leaves)
            if self.hier:
                for idx in buckets:
                    red = self._hier_mean([leaves[i] for i in idx])
                    for j, i in enumerate(idx):
                        out[i] = red[j]
            else:
                for idx in buckets:
                    for i in idx:
                        out[i] = lax.pmean(self._qdq(leaves[i]),
                                           self.axis_name)
            return jax.tree_util.tree_unflatten(treedef, out)
        if self.hier:
            return self._hier_stateful(leaves, treedef, buckets, ef)
        ef_leaves = jax.tree_util.tree_leaves(ef)
        if len(ef_leaves) != len(leaves):
            raise ValueError(
                f"error-feedback state has {len(ef_leaves)} leaves for a "
                f"{len(leaves)}-leaf grad tree — engine state was not "
                "initialized with init_ef"
            )
        out = [None] * len(leaves)
        new_ef = [None] * len(leaves)
        for idx in buckets:
            # one codec application + one collective per bucket: the EF
            # residuals stay keyed to exactly this bucket's leaves
            sub = [leaves[i] for i in idx]
            esub = [ef_leaves[i] for i in idx]
            wire, e2 = self.codec.compress_stacked(sub, esub)
            red = lax.pmean(wire, self.axis_name)
            for j, i in enumerate(idx):
                out[i] = red[j]
                new_ef[i] = e2[j]
        return (
            jax.tree_util.tree_unflatten(treedef, out),
            jax.tree_util.tree_unflatten(treedef, new_ef),
        )

    def _hier_stateful(self, leaves, treedef, buckets, ef):
        """The bucketed+hier+``:ef`` composition: per bucket, pack the
        grads flat, in-slice reduce-scatter, ``compress_stacked`` the
        DCN shard against that bucket's residual row, cross-slice psum,
        in-slice all-gather, unpack. ``ef`` is one ``(1, seg_b)`` array
        per bucket (hier_ef_template ordering — assign_buckets order)."""
        dcn_axis, ici_axis = tuple(self.axis_name)
        r, s = self.axis_sizes
        n = r * s
        ef_leaves = jax.tree_util.tree_leaves(ef)
        if len(ef_leaves) != len(buckets):
            raise ValueError(
                f"hier error-feedback state has {len(ef_leaves)} shard "
                f"rows for a {len(buckets)}-bucket schedule — engine "
                "state was not initialized with hier_ef_template"
            )
        out = [None] * len(leaves)
        new_ef = []
        for b, idx in enumerate(buckets):
            sub = [leaves[i] for i in idx]
            flat = _pack_flat(sub)
            L = flat.shape[0]
            seg = hier_segment(L, s)
            if s > 1:
                buf = jnp.zeros((s * seg,), flat.dtype).at[:L].set(flat)
                shard = lax.psum_scatter(buf, ici_axis,
                                         scatter_dimension=0, tiled=True)
            else:
                shard = flat
            e2 = ef_leaves[b]
            if r > 1:
                wire, e2 = self.codec.compress_stacked(shard, e2)
                shard = lax.psum(wire, dcn_axis)
            shard = shard / n
            red = (lax.all_gather(shard, ici_axis, tiled=True)[:L]
                   if s > 1 else shard)
            for j, leaf in zip(idx, _unpack_flat(red, sub)):
                out[j] = leaf
            new_ef.append(e2)
        return (
            jax.tree_util.tree_unflatten(treedef, out),
            tuple(new_ef),
        )


def bucketed(name: str, axis_name, axis_size: int, bucket_mb: float,
             codec=None, axis_sizes=None) -> BucketedOverlapSync:
    """``--allreduce-buckets`` entry: validate the (strategy, codec)
    pair and return the bucketed scheduler. psum and hier only — the
    explicit ring variants already own a segmented hop schedule that a
    leaf-bucket layer would fight, and checked-mode AD has no exchanger
    collective to bucket (callers gate on that)."""
    codec = _resolve_codec(name, codec)
    key = _ALIASES.get(name, name)
    if key == "hier":
        _check_hier_axes(axis_name, axis_sizes, axis_size)
        return BucketedOverlapSync(axis_name, bucket_mb=bucket_mb,
                                   codec=codec, axis_sizes=axis_sizes)
    del axis_size  # collectives are axis-name driven; kept for symmetry
    if key != "psum":
        raise ValueError(
            f"--allreduce-buckets needs strategy 'psum' or 'hier' (got "
            f"{name!r}): the explicit ring variants already schedule "
            "their own segments, and compressed wires ride the codec "
            "knob (--wire-codec) on the psum path"
        )
    return BucketedOverlapSync(axis_name, bucket_mb=bucket_mb, codec=codec)


# --------------------------------------------------------------------------
# registry — reference config names kept as aliases (SURVEY.md §5.6:
# exch_strategy: 'ar'|'cudaaware'|'asa32'|'asa16'|'nccl32')
# --------------------------------------------------------------------------

_CANONICAL = {
    "psum": lambda axis, size: psum_mean(axis),
    "psum_bf16": lambda axis, size: psum_bf16(axis),
    "ring": ring,
    "ring_bf16": ring_bf16,
    "ring_int8": ring_int8,
}

_ALIASES = {
    "ar": "psum",
    "cudaaware": "psum",
    "copper": "psum",
    "nccl32": "psum",
    "nccl16": "psum_bf16",
    "asa32": "ring",
    "asa16": "ring_bf16",
}


_ALREADY_COMPRESSED = ("psum_bf16", "ring_bf16", "ring_int8")


def _resolve_codec(name: str, codec):
    """Validate a (strategy, codec) pair -> WireCodec. Strategies that
    hard-code their own wire compression refuse a second codec; the
    explicit ring takes its wire FROM the codec (the asa16 special case
    generalized) but has no leaf-level residual to feed back — each hop
    re-quantizes partial sums per segment — so ``:ef`` needs the psum
    path."""
    from theanompi_tpu.parallel.codec import get_codec

    codec = get_codec(codec)
    key = _ALIASES.get(name, name)
    if not codec.active:
        return codec
    if key in _ALREADY_COMPRESSED:
        raise ValueError(
            f"strategy {name!r} already compresses its wire; composing it "
            f"with --wire-codec {codec.spec!r} would quantize twice — use "
            "strategy 'psum' (or 'ring') with the codec, or the strategy "
            "alone"
        )
    if key == "ring" and codec.error_feedback:
        raise ValueError(
            "error feedback needs a per-leaf residual, but the explicit "
            "ring quantizes per segment per hop (no stable leaf mapping) "
            f"— use --wire-codec {codec.name!r} on the ring, or "
            f"{codec.spec!r} with strategy 'psum'"
        )
    return codec


def checked_mode_strategy(name: str, axis_name, axis_size: int,
                          codec=None) -> Strategy:
    """The ``check_vma=True`` exchanger (migration plan above, executed
    for the BSP engine in round 5 — ``parallel/bsp.py::_checked_vma``):
    AD already delivers the replicated-param cotangent globally SUMMED,
    so the psum family degenerates to division by the axis size with no
    collective. The explicit ring/compressed strategies have no wire to
    compress in this mode (there is no exchanger collective at all) and
    are refused — per the plan they survive only as weight-exchange
    collectives (EASGD/GoSGD averaging)."""
    del axis_name
    if _resolve_codec(name, codec).active:
        raise ValueError(
            "checked-mode (check_vma=True) gradient sync has no exchanger "
            "collective — there is no wire for a codec to compress; drop "
            "--wire-codec or run the classic semantics"
        )
    key = _ALIASES.get(name, name)
    # 'hier' degenerates with the psum family: AD already summed over
    # every mesh axis, so there is no two-hop schedule left to stage
    if key in ("psum", "psum_bf16", "hier"):
        return lambda grads: jax.tree_util.tree_map(
            lambda g: g / axis_size, grads
        )
    raise ValueError(
        f"strategy {name!r} has no checked-mode (check_vma=True) gradient-"
        "sync form: AD already summed the cotangents, so there is no "
        "exchanger collective to segment or compress — use 'psum', or run "
        "the classic semantics (TMPI_CHECKED_VMA unset)"
    )


def get_strategy(name: str, axis_name, axis_size: int,
                 codec=None, axis_sizes=None) -> Strategy:
    """``axis_name`` may be a tuple of mesh axes (multi-slice BSP): the
    psum family reduces over all of them (XLA lowers ICI-then-DCN); the
    explicit ring variants are single-axis algorithms by construction;
    ``hier`` REQUIRES the 2-axis ``(dcn, data)`` form plus
    ``axis_sizes=(n_slices, per_slice)`` and stages the hierarchy
    explicitly (codec on the DCN hop only).

    ``codec``: a wire codec spec/instance (parallel/codec.py). On the
    psum path it returns the STATEFUL compressed strategy (error
    feedback threaded through engine state); on the explicit ring it
    selects the ring's wire compression (the asa16 special case,
    generalized); strategies that already compress refuse it."""
    codec = _resolve_codec(name, codec)
    key = _ALIASES.get(name, name)
    if key == "hier":
        _check_hier_axes(axis_name, axis_sizes, axis_size)
        return hierarchical_sync(tuple(axis_name), tuple(axis_sizes),
                                 codec)
    if not isinstance(axis_name, str) and key in ("ring", "ring_bf16", "ring_int8"):
        raise ValueError(
            f"strategy {name!r} is a single-axis ring; on a multi-slice "
            "mesh use 'psum'/'psum_bf16' (XLA lowers the ICI/DCN "
            "hierarchy from the mesh layout) or 'hier' (explicit staged "
            "schedule, codec on the DCN hop)"
        )
    if codec.active:
        if key == "psum":
            return codec_psum_mean(axis_name, codec)
        # key == "ring" (every other pairing raised in _resolve_codec)
        return _packed(
            lambda flat: _ring_allreduce_flat(
                flat, axis_name, axis_size, wire=codec.name
            ) / axis_size
        )
    try:
        return _CANONICAL[key](axis_name, axis_size)
    except KeyError:
        raise ValueError(
            f"unknown exchange strategy {name!r}; available: "
            f"{sorted(_CANONICAL) + ['hier'] + sorted(_ALIASES)}"
        ) from None
