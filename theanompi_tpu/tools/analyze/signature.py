"""Collective-signature extraction from jaxprs.

The analyzer's core primitive: given a traced step (``jax.make_jaxpr``
over abstract operands — nothing compiled, nothing executed), walk the
equation graph and produce the ordered list of collectives the program
will post, with enough detail to verify them:

- **what**: primitive name, axis names, operand dtype/shape;
- **how often**: the static execution multiplier (``lax.scan`` /
  static ``fori_loop`` bodies multiply by their trip count);
- **wire honesty**: whether low-bit quantization evidence (int8/bf16
  intermediates — the codec layer's in-graph footprint) feeds the
  operand, so the traffic cross-check can price value-space compressed
  collectives the way ``obs/comm.py`` does;
- **where**: the user source line (for findings and per-line
  ``spmd_exempt`` suppressions).

Alongside the signature the walk runs a replicated-vs-varying dataflow
analysis — the classic SPMD uniformity question. Seeds: ``shard_map``
invars whose ``in_specs`` entry names a mesh axis are varying (each device holds a
different shard), ``axis_index``/``ppermute``/``reduce_scatter``/
``all_to_all`` outputs are varying; ``psum``/``all_gather``/``pmin``/
``pmax`` outputs are uniform (every rank computes the same value).
A ``cond`` whose predicate is varying and whose branches post
DIFFERENT collective sequences — or a ``while`` whose predicate is
varying with collectives in its body — is the deadlock class
(rule SPMD002): ranks can disagree about which collectives to enter.
A varying ``cond`` whose branches carry identical collective
sequences is safe (the same schedule executes either way), matching
the rule the reference's gang-scheduled exchanges implicitly relied
on.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

# low-bit dtypes that count as quantization evidence (the codec layer's
# int8 block kernels / bf16 casts); fp8 variants included for when the
# codec grows them
_QUANT_DTYPES = ("int8", "uint8", "bfloat16", "float8")

# collective primitives and their uniformity/wire semantics
COLLECTIVE_PRIMS = {
    "psum", "pmin", "pmax", "ppermute", "all_gather", "reduce_scatter",
    "all_to_all", "pgather",
}
# output identical on every participating rank
_UNIFORM_OUT = {"psum", "pmin", "pmax", "all_gather"}
# primitives whose OUTPUT differs per rank even on uniform input
_VARYING_OUT = {"ppermute", "reduce_scatter", "all_to_all", "axis_index",
                "pgather"}
# subjaxpr-carrying primitives we deliberately do not descend into
_OPAQUE = {"pallas_call"}


@dataclass(frozen=True)
class Collective:
    """One collective in program order."""

    prim: str
    axes: tuple  # participating mesh axis names
    dtype: str  # operand dtype (output dtype for all_gather)
    shape: tuple  # operand shape (output shape for all_gather)
    count: int  # static execution multiplier (scan trip products)
    quantized: bool = False  # low-bit evidence upstream of the operand
    file: str = ""
    line: int = 0

    def key(self) -> tuple:
        """Identity for golden comparison / branch-sequence equality —
        deliberately excludes source location and quantization evidence
        (the golden pins the SCHEDULE, per codec config)."""
        return (self.prim, self.axes, self.dtype, self.shape, self.count)

    def as_json(self) -> dict:
        return {"prim": self.prim, "axes": list(self.axes),
                "dtype": self.dtype, "shape": list(self.shape),
                "count": self.count}


@dataclass
class ControlFlowIssue:
    """A collective under potentially rank-divergent control flow
    (rule SPMD002 input)."""

    kind: str  # 'cond-mismatch' | 'while-collective'
    detail: str
    file: str = ""
    line: int = 0


@dataclass
class Signature:
    collectives: list = field(default_factory=list)
    issues: list = field(default_factory=list)

    def keys(self) -> list:
        return [c.key() for c in self.collectives]

    def as_json(self) -> list:
        return [c.as_json() for c in self.collectives]


def _source_of(eqn) -> tuple:
    """Best-effort (file, line) of the user frame that built ``eqn``."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return frame.file_name, int(frame.start_line)
    except Exception:  # noqa: BLE001 — source info is advisory only
        pass
    return "", 0


def _axis_tuple(eqn) -> tuple:
    ax = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
    if isinstance(ax, str):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _subjaxprs(value):
    """Every Jaxpr/ClosedJaxpr reachable from one eqn param value."""
    out = []
    stack = [value]
    while stack:
        v = stack.pop()
        # ClosedJaxpr exposes .eqns too — unwrap to the open Jaxpr first
        if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
    return out


def _eqn_is_quant_marker(eqn) -> bool:
    """Does this eqn (or any jaxpr nested in its params) produce a
    low-bit value? That's the codec layer's in-graph footprint — the
    quantize/dequantize chain around a value-space compressed
    collective."""
    def has_quant(jaxpr) -> bool:
        for e in jaxpr.eqns:
            for v in e.outvars:
                dt = getattr(getattr(v, "aval", None), "dtype", None)
                if dt is not None and str(dt).startswith(_QUANT_DTYPES):
                    return True
            for pv in e.params.values():
                for sub in _subjaxprs(pv):
                    if has_quant(sub):
                        return True
        return False

    for v in eqn.outvars:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and str(dt).startswith(_QUANT_DTYPES):
            return True
    for pv in eqn.params.values():
        for sub in _subjaxprs(pv):
            if has_quant(sub):
                return True
    return False


def _spec_shards(spec) -> bool:
    """Does a shard_map PartitionSpec shard ANY dim over a mesh axis?"""
    return any(entry is not None for entry in spec)


class _Walker:
    """Recursive jaxpr walk threading three per-var facts: ``varying``
    (may differ across ranks) and ``quant`` (low-bit evidence
    upstream), plus the enclosing mesh's axis sizes."""

    def __init__(self):
        self.sig = Signature()
        self.axis_sizes: dict = {}

    # -- per-var fact helpers ----------------------------------------------
    @staticmethod
    def _get(facts: dict, var) -> bool:
        # Literals are uniform and unquantized
        return facts.get(id(var), False) if hasattr(var, "aval") and not \
            hasattr(var, "val") else False

    @staticmethod
    def _set(facts: dict, var, val: bool) -> None:
        facts[id(var)] = bool(val)

    # -- main walk ----------------------------------------------------------
    def walk(self, jaxpr, varying: dict, quant: dict, mult: int):
        """``jaxpr``: core.Jaxpr; ``varying``/``quant``: id(var)->bool
        maps pre-seeded for ``jaxpr.invars``."""
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_vary = any(self._get(varying, v) for v in eqn.invars)
            in_quant = any(self._get(quant, v) for v in eqn.invars)

            if name in COLLECTIVE_PRIMS:
                self._record_collective(eqn, mult, in_quant)
            if name == "shard_map":
                self._walk_shard_map(eqn, varying, quant, mult)
                continue
            if name == "jit":
                self._walk_mapped(eqn.params["jaxpr"].jaxpr, eqn, varying,
                                  quant, mult)
                continue
            if name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                self._walk_mapped(body, eqn, varying, quant,
                                  mult * int(eqn.params.get("length", 1)))
                continue
            if name == "while":
                self._walk_while(eqn, varying, quant, mult)
                continue
            if name == "cond":
                self._walk_cond(eqn, varying, quant, mult)
                continue
            if name not in _OPAQUE:
                # generic subjaxpr-carrying prims (custom_jvp/vjp, remat,
                # closed_call...): descend conservatively
                for pv in eqn.params.values():
                    for sub in _subjaxprs(pv):
                        sv, sq = {}, {}
                        if len(sub.invars) == len(eqn.invars):
                            for si, oi in zip(sub.invars, eqn.invars):
                                self._set(sv, si, self._get(varying, oi))
                                self._set(sq, si, self._get(quant, oi))
                        else:
                            for si in sub.invars:
                                self._set(sv, si, in_vary)
                                self._set(sq, si, in_quant)
                        self.walk(sub, sv, sq, mult)

            # forward fact propagation for this eqn's outputs
            out_vary = in_vary
            if name in _UNIFORM_OUT:
                out_vary = False
            elif name in _VARYING_OUT:
                out_vary = True
            if name in COLLECTIVE_PRIMS:
                # quantization evidence applies to the wire the operand
                # just CROSSED, not to every later collective in the
                # chain: a reduced output is a fresh value (the hier
                # strategy's in-slice all-gather after its codec'd DCN
                # psum rides fp32 and must be priced fp32). The output
                # stays marked only if it is itself low-bit (physical
                # compressed wire, e.g. a bf16 psum result).
                out_quant = _eqn_is_quant_marker(eqn)
            else:
                out_quant = in_quant or _eqn_is_quant_marker(eqn)
            for v in eqn.outvars:
                self._set(varying, v, out_vary)
                self._set(quant, v, out_quant)

    # -- collectives ---------------------------------------------------------
    def _record_collective(self, eqn, mult: int, quantized: bool) -> None:
        axes = _axis_tuple(eqn)
        # one Collective per operand: a single psum eqn can carry a whole
        # pytree's leaves (lax.pmean over a tree). all_gather's wire is
        # sized by its OUTPUTS (the gathered buffers); everything else by
        # the operands.
        refs = eqn.outvars if eqn.primitive.name == "all_gather" else \
            eqn.invars
        f, ln = _source_of(eqn)
        for ref in refs:
            aval = getattr(ref, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            self.sig.collectives.append(Collective(
                prim=eqn.primitive.name, axes=axes,
                dtype=str(aval.dtype), shape=tuple(aval.shape),
                count=int(mult), quantized=bool(quantized),
                file=f, line=ln,
            ))

    # -- structured descent --------------------------------------------------
    def _walk_mapped(self, body, eqn, varying, quant, mult) -> None:
        """Descend into a subjaxpr whose invars map 1:1 onto the last
        ``len(body.invars)`` eqn invars (pjit, scan: consts+carry+xs)."""
        sv, sq = {}, {}
        ops = eqn.invars[-len(body.invars):] if body.invars else []
        for si, oi in zip(body.invars, ops):
            self._set(sv, si, self._get(varying, oi))
            self._set(sq, si, self._get(quant, oi))
        self.walk(body, sv, sq, mult)
        inner_out = body.outvars[-len(eqn.outvars):] if eqn.outvars else []
        for ov, iv in zip(eqn.outvars, inner_out):
            self._set(varying, ov, self._get(sv, iv))
            self._set(quant, ov, self._get(sq, iv))

    def _walk_shard_map(self, eqn, varying, quant, mult) -> None:
        body = eqn.params["jaxpr"]
        body = body.jaxpr if hasattr(body, "jaxpr") else body
        mesh = eqn.params.get("mesh")
        if mesh is not None:
            try:
                self.axis_sizes.update(dict(mesh.shape))
            except Exception:  # noqa: BLE001
                pass
        in_specs = eqn.params.get("in_specs", ())
        sv, sq = {}, {}
        for i, si in enumerate(body.invars):
            # any named axis in the PartitionSpec -> per-device shard
            sharded = i < len(in_specs) and _spec_shards(in_specs[i])
            oi = eqn.invars[i] if i < len(eqn.invars) else None
            self._set(sv, si, sharded or (oi is not None
                                          and self._get(varying, oi)))
            self._set(sq, si, oi is not None and self._get(quant, oi))
        self.walk(body, sv, sq, mult)
        out_specs = eqn.params.get("out_specs", ())
        for i, ov in enumerate(eqn.outvars):
            self._set(varying, ov,
                      i < len(out_specs) and _spec_shards(out_specs[i]))
            self._set(quant, ov, False)

    def _extract_branch(self, branch, eqn, varying, quant, mult):
        """Walk one cond branch in an isolated Walker; returns its
        signature (collectives recorded in order)."""
        sub = _Walker()
        sub.axis_sizes = self.axis_sizes
        body = branch.jaxpr if hasattr(branch, "jaxpr") else branch
        sv, sq = {}, {}
        ops = eqn.invars[1:]  # invars[0] is the branch index / predicate
        for si, oi in zip(body.invars, ops):
            sub._set(sv, si, self._get(varying, oi))
            sub._set(sq, si, self._get(quant, oi))
        sub.walk(body, sv, sq, mult)
        return sub.sig

    def _walk_cond(self, eqn, varying, quant, mult) -> None:
        pred = eqn.invars[0]
        pred_varying = self._get(varying, pred)
        branches = eqn.params.get("branches", ())
        sigs = [self._extract_branch(b, eqn, varying, quant, mult)
                for b in branches]
        for s in sigs:
            self.sig.issues.extend(s.issues)
        seqs = [s.keys() for s in sigs]
        if pred_varying and any(s for s in seqs) and not all(
                s == seqs[0] for s in seqs):
            f, ln = _source_of(eqn)
            self.sig.issues.append(ControlFlowIssue(
                kind="cond-mismatch",
                detail=(
                    "cond predicate may differ across ranks and its "
                    f"branches post different collective sequences "
                    f"{[[k[0] for k in s] for s in seqs]} — ranks taking "
                    "different branches would deadlock the gang"
                ),
                file=f, line=ln,
            ))
        if sigs:
            # signature determinism: record the heaviest branch (they are
            # identical in the safe cases the engines actually trace)
            best = max(sigs, key=lambda s: sum(
                int(np.prod(c.shape or (1,))) * c.count
                for c in s.collectives))
            self.sig.collectives.extend(best.collectives)
        in_vary = any(self._get(varying, v) for v in eqn.invars)
        in_quant = any(self._get(quant, v) for v in eqn.invars)
        for v in eqn.outvars:
            self._set(varying, v, in_vary)
            self._set(quant, v, in_quant)

    def _walk_while(self, eqn, varying, quant, mult) -> None:
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        cond_body = cond_j.jaxpr if hasattr(cond_j, "jaxpr") else cond_j
        body = body_j.jaxpr if hasattr(body_j, "jaxpr") else body_j
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        carry_ops = eqn.invars[cn + bn:]
        # is any input the loop predicate can see varying?
        cond_ops = list(eqn.invars[:cn]) + list(carry_ops)
        pred_varying = any(self._get(varying, v) for v in cond_ops)
        sub = _Walker()
        sub.axis_sizes = self.axis_sizes
        sv, sq = {}, {}
        body_ops = list(eqn.invars[cn:cn + bn]) + list(carry_ops)
        for si, oi in zip(body.invars, body_ops):
            sub._set(sv, si, self._get(varying, oi))
            sub._set(sq, si, self._get(quant, oi))
        sub.walk(body, sv, sq, mult)
        self.sig.issues.extend(sub.sig.issues)
        if sub.sig.collectives and pred_varying:
            f, ln = _source_of(eqn)
            self.sig.issues.append(ControlFlowIssue(
                kind="while-collective",
                detail=(
                    "while-loop body posts collectives "
                    f"({sorted({c.prim for c in sub.sig.collectives})}) "
                    "but its trip count depends on rank-varying data — "
                    "ranks can disagree on the iteration count and "
                    "deadlock mid-loop"
                ),
                file=f, line=ln,
            ))
        self.sig.collectives.extend(sub.sig.collectives)
        for v in eqn.outvars:
            self._set(varying, v, True)  # conservative
            self._set(quant, v, any(self._get(sq, bv)
                                    for bv in body.invars))


def extract_signature(closed_jaxpr) -> tuple:
    """Walk a ClosedJaxpr (as returned by ``jax.make_jaxpr``) ->
    ``(Signature, axis_sizes)``. Top-level invars are uniform (the
    host passes every rank the same global operands; sharding only
    happens at ``shard_map`` boundaries)."""
    w = _Walker()
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else \
        closed_jaxpr
    varying: dict = {}
    quant: dict = {}
    for v in jaxpr.invars:
        w._set(varying, v, False)
        w._set(quant, v, False)
    w.walk(jaxpr, varying, quant, 1)
    return w.sig, dict(w.axis_sizes)


# --------------------------------------------------------------------------
# wire-byte accounting (the jaxpr-side mirror of obs/comm.py's
# closed-form models): bytes SENT per device per execution
# --------------------------------------------------------------------------


def _axis_prod(axes: tuple, axis_sizes: dict) -> int:
    n = 1
    for a in axes:
        n *= int(axis_sizes.get(a, 1))
    return n


def collective_wire_bytes(c: Collective, axis_sizes: dict) -> float:
    """Per-device bytes one execution of ``c`` puts on the wire, using
    the same ring-lowering convention as obs/comm.py: allreduce
    ``2(n-1)/n·B``, gather/scatter halves ``(n-1)/n·B``, ppermute ``B``
    (each device forwards its buffer once)."""
    n = _axis_prod(c.axes, axis_sizes)
    if n <= 1:
        return 0.0
    elems = int(np.prod(c.shape or (1,)))
    try:
        itemsize = np.dtype(c.dtype).itemsize
    except TypeError:
        import jax.numpy as jnp

        itemsize = jnp.dtype(c.dtype).itemsize
    nbytes = float(elems * itemsize)
    if c.prim in ("psum", "pmin", "pmax"):
        return 2.0 * (n - 1) / n * nbytes
    if c.prim in ("all_gather", "reduce_scatter", "all_to_all", "pgather"):
        return (n - 1) / n * nbytes
    if c.prim == "ppermute":
        return nbytes
    return nbytes


def collective_link_bytes(c: Collective, axis_sizes: dict,
                          dcn_axis: str = "dcn") -> dict:
    """Split one collective's per-device wire bytes by link class:
    ``{"ici": ..., "dcn": ...}``. Axes that don't include ``dcn_axis``
    are pure-ICI; a collective purely over ``dcn_axis`` is pure-DCN.
    For a mixed-axis collective (flat allreduce over ('dcn','data')) a
    ring over the combined axis crosses a slice boundary on ``r-1`` of
    its ``n-1`` hops, so the DCN share of the wire is ``(r-1)/(n-1)``
    for both the allreduce and one-sided forms — the same convention as
    obs/comm.py's ``dcn_fraction``. A ppermute whose axes span slices
    is priced all-DCN (worst case: every neighbor hop may cross)."""
    total = collective_wire_bytes(c, axis_sizes)
    out = {"ici": 0.0, "dcn": 0.0}
    if total <= 0.0:
        return out
    if dcn_axis not in c.axes:
        out["ici"] = total
        return out
    n = _axis_prod(c.axes, axis_sizes)
    r = int(axis_sizes.get(dcn_axis, 1))
    s = max(1, n // max(1, r))
    if s == 1 or r <= 1:
        out["dcn"] = total if r > 1 else 0.0
        out["ici"] = total - out["dcn"]
        return out
    if c.prim == "ppermute":
        out["dcn"] = total
        return out
    frac = (r - 1) / (n - 1) if n > 1 else 0.0
    out["dcn"] = total * frac
    out["ici"] = total - out["dcn"]
    return out


def signature_link_bytes(sig: Signature, axis_sizes: dict,
                         dcn_axis: str = "dcn") -> dict:
    """Per-link-class raw wire bytes per execution, dtype-honest:
    ``{"ici": ..., "dcn": ...}`` totals over all collectives (count-
    weighted). ``ici + dcn == signature_raw_bytes`` by construction."""
    out = {"ici": 0.0, "dcn": 0.0}
    for c in sig.collectives:
        lb = collective_link_bytes(c, axis_sizes, dcn_axis)
        out["ici"] += lb["ici"] * c.count
        out["dcn"] += lb["dcn"] * c.count
    return out


def signature_raw_bytes(sig: Signature, axis_sizes: dict) -> float:
    """Total per-device wire bytes per execution, dtype-honest (what
    the traced program physically moves, fp32 for value-space-codec
    operands)."""
    return sum(collective_wire_bytes(c, axis_sizes) * c.count
               for c in sig.collectives)


def signature_effective_bytes(sig: Signature, axis_sizes: dict,
                              codec_bytes_per_element: float) -> float:
    """Codec-aware wire bytes: collectives whose operands carry low-bit
    quantization evidence but ride fp32 lanes (value-space compression
    — psum/reduce_scatter/all_gather on qdq'd values) are priced at the
    codec's analytic bytes-per-element, matching obs/comm.py's
    accounting convention; already-low-bit operands (the packed gossip
    / ring messages) are physical and keep their dtype bytes."""
    total = 0.0
    for c in sig.collectives:
        b = collective_wire_bytes(c, axis_sizes) * c.count
        try:
            itemsize = np.dtype(c.dtype).itemsize
        except TypeError:
            import jax.numpy as jnp

            itemsize = jnp.dtype(c.dtype).itemsize
        if c.quantized and itemsize >= 4:
            b *= codec_bytes_per_element / 4.0
        total += b
    return total


def has_quantized_collective(sig: Signature) -> bool:
    """Any collective carrying quantization evidence — either value-
    space (fp32 operand, low-bit upstream) or physical (low-bit
    operand dtype)."""
    for c in sig.collectives:
        if c.quantized:
            return True
        if str(c.dtype).startswith(_QUANT_DTYPES):
            return True
    return False


# --------------------------------------------------------------------------
# donation extraction
# --------------------------------------------------------------------------


def donated_flags(closed_jaxpr, n_leading: Optional[int] = None) -> tuple:
    """The ``donated_invars`` tuple of the outermost jit equation (the
    jitted step), optionally truncated to the first ``n_leading``
    entries (= the flattened state argument's leaves)."""
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else \
        closed_jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            d = tuple(eqn.params.get("donated_invars", ()))
            return d[:n_leading] if n_leading is not None else d
    return ()
