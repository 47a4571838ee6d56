# The numerics half's spine. The AST half judges source text, the
# trace half judges shardings/collectives/signatures — but the two
# worst correctness bugs this repo ever shipped (a bf16 microbatch
# gradient sum dropping small-gradient tails, then an f32 "fix"
# silently discarding the imaginary part of complex gradients; both
# found by hand in PR 4) lived in neither place. They were NUMERICS-
# FLOW facts: the dtype of an accumulator, the placement of a cast,
# properties of the traced program's dataflow that no source pattern
# and no compiled layout exposes. This module models exactly that: a
# ValueGraph over a jaxpr (sub-jaxprs walked with dataflow stitched
# across scan/pjit/cond/while boundaries) that auditors query for
# "what dtype does this reduction carry" and "does this cast reach
# that output". Baselining reuses the trace half's fingerprint format
# and "no NEW findings" gate with the numerics baseline file.
"""Numerics-audit core: NumericsProgram, ValueGraph, auditor base."""
from pathlib import Path
import dataclasses
import typing as tp

from ..trace.core import (TraceFinding, load_trace_baseline,
                          new_trace_findings, run_auditors,
                          save_trace_baseline, trace_fingerprint)

__all__ = [
    "DEFAULT_NUMERICS_BASELINE_NAME", "NumericsAuditor", "NumericsFinding",
    "NumericsProgram", "ValueGraph", "is_complex", "is_narrow_float",
    "load_numerics_baseline", "new_numerics_findings", "numerics_fingerprint",
    "run_numerics_auditors", "save_numerics_baseline",
]

# One record type across the trace and numerics halves: a finding is
# (code, program label, stable key, message, hint) either way, and the
# shared fingerprint/baseline machinery consumes it unchanged.
NumericsFinding = TraceFinding
numerics_fingerprint = trace_fingerprint
load_numerics_baseline = load_trace_baseline
new_numerics_findings = new_trace_findings
run_numerics_auditors = run_auditors

DEFAULT_NUMERICS_BASELINE_NAME = ".analysis-numerics-baseline.json"


def save_numerics_baseline(path: Path,
                           findings: tp.Sequence[TraceFinding]) -> None:
    save_trace_baseline(
        path, findings,
        comment=("flashy_tpu.analysis numerics baseline — grandfathered "
                 "FT2xx findings; the gate is 'no NEW findings'. "
                 "Regenerate with --numerics --write-baseline."))


# ----------------------------------------------------------------------
# dtype predicates
# ----------------------------------------------------------------------
def _np_dtype(dtype: tp.Any) -> tp.Any:
    import numpy as np
    try:
        return np.dtype(dtype)
    except TypeError:
        return None  # extended dtypes (prng keys) have no numpy spelling


def is_narrow_float(dtype: tp.Any) -> bool:
    """True for float dtypes narrower than f32 (bf16, f16, the f8s) —
    the accumulator widths whose partial sums shed addend mantissa bits
    long before the microbatch count looks suspicious."""
    import jax.numpy as jnp
    np_dtype = _np_dtype(dtype)
    if np_dtype is None or not jnp.issubdtype(np_dtype, jnp.floating):
        return False
    return jnp.finfo(np_dtype).bits < 32


def is_complex(dtype: tp.Any) -> bool:
    import jax.numpy as jnp
    np_dtype = _np_dtype(dtype)
    return np_dtype is not None and jnp.issubdtype(np_dtype,
                                                   jnp.complexfloating)


def is_prng_key(aval: tp.Any) -> bool:
    import jax
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    try:
        return jax.dtypes.issubdtype(dtype, jax.dtypes.prng_key)
    except TypeError:
        return False


# Primitives that move/reinterpret values without arithmetic on them:
# a scale (or a cast) flowing through these is still "the same value"
# for placement purposes. `convert_element_type` is included on
# purpose — a cast changes precision, not identity, and the cast
# checks track converts explicitly.
DATA_MOVEMENT_PRIMS = frozenset({
    "broadcast_in_dim", "concatenate", "convert_element_type", "copy",
    "dynamic_slice", "expand_dims", "gather", "pad", "reshape", "rev",
    "slice", "squeeze", "transpose",
    # Pallas ref traffic: `get` reads a value out of a memory ref and
    # `swap` writes one in — inside a kernel body they are the moves
    # between HBM/VMEM refs and values, arithmetic-free. The graph
    # models a write as the ref ALSO being an output of its swap (see
    # REF_WRITE_PRIMS), so a value's identity survives a
    # write-then-read round trip through scratch.
    "get", "swap",
    # an async copy moves a window of one ref into another (HBM pool
    # block -> VMEM tile); ValueGraph rewires it source ref -> written
    # ref (see _dma_endpoints)
    "dma_start",
})

# Ref-mutating primitives (pallas kernel bodies): the written ref is
# syntactically an INVAR, but for dataflow it is an output — later
# `get`s of the ref read what the swap stored. ValueGraph appends the
# ref token to these nodes' outputs so forward/backward closures cross
# the write.
REF_WRITE_PRIMS = frozenset({"swap", "masked_swap"})

# Reduction primitives whose operand dtype IS the accumulation dtype:
# an elementwise add chain can be audited via its carry, but these
# reduce internally, so a narrow operand means a narrow accumulator.
# Covers the in-program reductions plus the cross-device ones (psum /
# reduce-scatter operands of a gradient sync).
REDUCTION_PRIMS = frozenset({
    "reduce_sum", "cumsum", "psum", "psum2", "all_reduce",
    "reduce_scatter", "reduce_precision_sum",
})

ADD_PRIMS = frozenset({"add", "add_any"})


# ----------------------------------------------------------------------
# the value graph
# ----------------------------------------------------------------------
@dataclasses.dataclass
class ScanInfo:
    """One scan eqn's carry wiring: (body invar, body outvar, outer
    outvar, outer init var-or-None) per carry position."""
    node: int
    context: str
    carries: tp.List[tp.Tuple[tp.Any, tp.Any, tp.Any, tp.Any]]


class ValueGraph:
    """Dataflow over a (closed) jaxpr, sub-jaxprs included.

    Nodes are eqn occurrences; values are (Var, context) TOKENS — jax
    caches traced sub-jaxprs, so the same body (and its Var objects)
    can appear under two different call sites, and a raw-Var graph
    would fuse those occurrences into one. Literals are constants and
    carry no flow. Boundary aliases stitch outer tokens to sub-jaxpr
    invar/outvar tokens for the higher-order primitives (scan, while,
    cond, pjit, custom_*, shard_map, remat); scan/while additionally
    get a LOOP alias from body carry outvars back to body carry invars
    so reachability models iteration. `context` strings name the
    nesting (`scan@3/`), which the RNG auditor uses to tell "consumed
    inside the loop" from "consumed once".
    """

    def __init__(self, jaxpr: tp.Any):
        inner = getattr(jaxpr, "jaxpr", jaxpr)
        self.prims: tp.List[str] = []
        self.contexts: tp.List[str] = []
        self.eqns: tp.List[tp.Any] = []
        self.node_in: tp.List[tp.List[tp.Any]] = []
        self.node_out: tp.List[tp.List[tp.Any]] = []
        self.uses: tp.Dict[tp.Any, tp.List[int]] = {}
        self.producer: tp.Dict[tp.Any, int] = {}
        self.fwd_alias: tp.Dict[tp.Any, tp.List[tp.Any]] = {}
        self.bwd_alias: tp.Dict[tp.Any, tp.List[tp.Any]] = {}
        self.loop_alias: tp.Dict[tp.Any, tp.List[tp.Any]] = {}
        self.scans: tp.List[ScanInfo] = []
        self.invars: tp.List[tp.Any] = [(v, "") for v in inner.invars]
        self.constvars: tp.List[tp.Any] = [(v, "")
                                           for v in inner.constvars]
        self.outvars: tp.List[tp.Any] = [(v, "") for v in inner.outvars
                                         if not _is_literal(v)]
        self._walk(inner, "")

    # -- construction ---------------------------------------------------
    def _alias(self, src: tp.Any, dst: tp.Any, loop: bool = False) -> None:
        if _is_literal(src[0]) or _is_literal(dst[0]):
            return
        table = self.loop_alias if loop else self.fwd_alias
        table.setdefault(src, []).append(dst)
        if not loop:
            self.bwd_alias.setdefault(dst, []).append(src)
            if _is_ref(src[0]) and _is_ref(dst[0]) \
                    and dst not in self.bwd_alias.get(src, ()):
                # a memory ref is ONE buffer on both sides of a call
                # boundary (kernel body -> loop body -> pl.when branch):
                # a write made inside is read outside and in sibling
                # bodies, so the alias runs both ways
                self._alias(dst, src)

    def _walk(self, jaxpr: tp.Any, context: str) -> None:
        for eqn in jaxpr.eqns:
            node = len(self.prims)
            name = eqn.primitive.name
            self.prims.append(name)
            self.contexts.append(context)
            self.eqns.append(eqn)
            ins = [(v, context) for v in eqn.invars if not _is_literal(v)]
            outs = [(v, context) for v in eqn.outvars]
            if name == "dma_start":
                # the copy's invars are [src ref, its indices, dst ref,
                # its indices, semaphores...]: for dataflow it reads the
                # source ref and WRITES the destination ref — later
                # `get`s of the destination see the source's content
                src, dst = _dma_endpoints(eqn)
                ins, outs = [(src, context)], [(dst, context)]
            if name in REF_WRITE_PRIMS and ins:
                # the mutated ref (operand 0) is a dataflow OUTPUT:
                # later reads of the ref see the stored value. The
                # eqn's natural outvars are the ref's OLD content —
                # they derive from the REF, not from the value being
                # stored, so alias them off the ref instead of making
                # them node outputs (a node output would hand the
                # stored value a direct false edge into the old
                # content; the flat ref token still over-approximates
                # across writes, which is the sound direction).
                for old in outs:
                    self._alias(ins[0], old)
                outs = [ins[0]]
            self.node_in.append(ins)
            self.node_out.append(outs)
            for token in ins:
                self.uses.setdefault(token, []).append(node)
            for token in outs:
                self.producer[token] = node
            self._walk_sub(eqn, node, context)

    def _walk_sub(self, eqn: tp.Any, node: int, context: str) -> None:
        name = eqn.primitive.name
        sub_context = f"{context}{name}@{node}/"

        def outer(var: tp.Any) -> tp.Tuple[tp.Any, str]:
            return (var, context)

        def inner(var: tp.Any) -> tp.Tuple[tp.Any, str]:
            return (var, sub_context)

        if name == "scan":
            body = _unwrap(eqn.params["jaxpr"])
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            for o_var, i_var in zip(eqn.invars, body.invars):
                self._alias(outer(o_var), inner(i_var))
            carries = []
            for i in range(ncar):
                b_in = inner(body.invars[nc + i])
                b_out = inner(body.outvars[i])
                outer_out = outer(eqn.outvars[i])
                outer_init = eqn.invars[nc + i]
                self._alias(b_out, outer_out)
                self._alias(b_out, b_in, loop=True)
                carries.append((b_in, b_out, outer_out,
                                None if _is_literal(outer_init)
                                else outer(outer_init)))
            for b_var, o_var in zip(body.outvars[ncar:],
                                    eqn.outvars[ncar:]):
                self._alias(inner(b_var), outer(o_var))
            self.scans.append(ScanInfo(node, sub_context, carries))
            self._walk(body, sub_context)
        elif name == "while":
            body = _unwrap(eqn.params["body_jaxpr"])
            cond = _unwrap(eqn.params["cond_jaxpr"])
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            carry = eqn.invars[cn + bn:]
            for o_var, i_var in zip(eqn.invars[cn:cn + bn], body.invars):
                self._alias(outer(o_var), inner(i_var))
            for i, o_var in enumerate(carry):
                self._alias(outer(o_var), inner(body.invars[bn + i]))
                self._alias(inner(body.outvars[i]), outer(eqn.outvars[i]))
                self._alias(inner(body.outvars[i]),
                            inner(body.invars[bn + i]), loop=True)
            for o_var, i_var in zip(eqn.invars[:cn], cond.invars):
                self._alias(outer(o_var), inner(i_var))
            for i, o_var in enumerate(carry):
                if cn + i < len(cond.invars):
                    self._alias(outer(o_var), inner(cond.invars[cn + i]))
            self._walk(body, sub_context)
            self._walk(cond, sub_context)
        elif name == "cond":
            branches = [_unwrap(b) for b in eqn.params.get("branches", ())]
            operands = eqn.invars[1:]
            for branch in branches:
                for o_var, i_var in zip(operands, branch.invars):
                    self._alias(outer(o_var), inner(i_var))
                for i_var, o_var in zip(branch.outvars, eqn.outvars):
                    self._alias(inner(i_var), outer(o_var))
                self._walk(branch, sub_context)
        elif name == "pallas_call":
            # The kernel body's invars are memory REFS laid out
            # [scalar-prefetch/index args, input refs, output refs,
            # scratch refs] while the call's invars are [scalar args,
            # inputs] and its outvars the outputs — grid_mapping holds
            # the counts. Stitch operand->ref and out-ref->result so a
            # quant scale (or a cast) keeps its identity across the
            # kernel boundary; in-body get/swap traffic is handled by
            # DATA_MOVEMENT_PRIMS / REF_WRITE_PRIMS. This is what lets
            # FT203 verify the scale-folding identity INSIDE the fused
            # paged-decode kernel instead of going vacuously silent on
            # a pallas rewrite.
            body = _unwrap(eqn.params["jaxpr"])
            mapping = eqn.params.get("grid_mapping")
            n_args = len(eqn.invars)
            n_index = getattr(mapping, "num_index_operands", 0)
            n_in = getattr(mapping, "num_inputs", n_args - n_index)
            n_out = getattr(mapping, "num_outputs", len(eqn.outvars))
            for o_var, i_var in zip(eqn.invars[:n_index + n_in],
                                    body.invars):
                self._alias(outer(o_var), inner(i_var))
            for j, o_var in enumerate(eqn.outvars[:n_out]):
                ref_pos = n_index + n_in + j
                if ref_pos < len(body.invars):
                    self._alias(inner(body.invars[ref_pos]),
                                outer(o_var))
            self._walk(body, sub_context)
        else:
            # pjit / closed_call / custom_jvp/vjp / remat / shard_map —
            # and any future higher-order primitive with a 1:1 calling
            # convention: stitch positionally when arities line up,
            # otherwise still walk the body (flow stays internal).
            for sub in _sub_jaxprs(eqn):
                if (len(sub.invars) == len(eqn.invars)
                        and len(sub.outvars) == len(eqn.outvars)):
                    for o_var, i_var in zip(eqn.invars, sub.invars):
                        self._alias(outer(o_var), inner(i_var))
                    for i_var, o_var in zip(sub.outvars, eqn.outvars):
                        self._alias(inner(i_var), outer(o_var))
                self._walk(sub, sub_context)

    # -- queries --------------------------------------------------------
    def dtype(self, token: tp.Any) -> tp.Any:
        return getattr(getattr(token[0], "aval", None), "dtype", None)

    def aval(self, token: tp.Any) -> tp.Any:
        return getattr(token[0], "aval", None)

    def forward(self, seeds: tp.Iterable[tp.Any],
                prims: tp.Optional[tp.FrozenSet[str]] = None,
                loop: bool = True) -> tp.Set[tp.Any]:
        """Vars reachable forward from `seeds` (seeds included). With
        `prims`, only eqns whose primitive is in the set propagate
        (alias edges always do)."""
        return self._closure(seeds, prims, forward=True, loop=loop)

    def backward(self, seeds: tp.Iterable[tp.Any],
                 prims: tp.Optional[tp.FrozenSet[str]] = None
                 ) -> tp.Set[tp.Any]:
        return self._closure(seeds, prims, forward=False, loop=True)

    def _closure(self, seeds: tp.Iterable[tp.Any],
                 prims: tp.Optional[tp.FrozenSet[str]],
                 forward: bool, loop: bool) -> tp.Set[tp.Any]:
        seen: tp.Set[tp.Any] = set(seeds)
        frontier = list(seen)
        while frontier:
            var = frontier.pop()
            next_vars: tp.List[tp.Any] = []
            alias = self.fwd_alias if forward else self.bwd_alias
            next_vars += alias.get(var, [])
            if loop:
                table = self.loop_alias
                if forward:
                    next_vars += table.get(var, [])
                else:
                    next_vars += [src for src, dsts in table.items()
                                  if var in dsts]
            if forward:
                for node in self.uses.get(var, []):
                    if prims is None or self.prims[node] in prims:
                        next_vars += self.node_out[node]
            else:
                node = self.producer.get(var)
                if node is not None and (prims is None
                                         or self.prims[node] in prims):
                    next_vars += self.node_in[node]
            for nxt in next_vars:
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        return seen

    def nodes_with_input(self, vars_set: tp.Set[tp.Any],
                         prims: tp.FrozenSet[str]) -> tp.List[int]:
        """Node ids (in walk order) of `prims` eqns consuming a var in
        `vars_set`."""
        out = []
        for node, prim in enumerate(self.prims):
            if prim in prims and any(v in vars_set
                                     for v in self.node_in[node]):
                out.append(node)
        return out

    def reaches(self, src_vars: tp.Iterable[tp.Any],
                dst_vars: tp.Set[tp.Any],
                prims: tp.Optional[tp.FrozenSet[str]] = None) -> bool:
        return bool(self.forward(src_vars, prims) & dst_vars)


def _is_literal(var: tp.Any) -> bool:
    return hasattr(var, "val") and not hasattr(var, "count")


def _is_ref(var: tp.Any) -> bool:
    return hasattr(getattr(var, "aval", None), "inner_aval")


def _dma_endpoints(eqn: tp.Any) -> tp.Tuple[tp.Any, tp.Any]:
    """(source ref var, destination ref var) of a pallas `dma_start`."""
    import jax

    parts = jax.tree_util.tree_unflatten(eqn.params["tree"], eqn.invars)
    return parts[0], parts[2]


def _unwrap(sub: tp.Any) -> tp.Any:
    """ClosedJaxpr | Jaxpr -> the plain Jaxpr with .eqns."""
    return sub.jaxpr if hasattr(sub, "jaxpr") else sub


def _sub_jaxprs(eqn: tp.Any) -> tp.Iterator[tp.Any]:
    for value in eqn.params.values():
        values = value if isinstance(value, (list, tuple)) else [value]
        for sub in values:
            # unwrap ClosedJaxpr FIRST: it forwards .eqns but not
            # .invars, so the plain Jaxpr is the only safe currency
            if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                yield sub.jaxpr
            elif hasattr(sub, "eqns"):
                yield sub


# ----------------------------------------------------------------------
# the audited program
# ----------------------------------------------------------------------
# Default FT203 role resolution: flattened-input path substrings. The
# paged cache's pool entries spell the leaves exactly this way
# (ops/paged_attention.pool_spec), so a program traced from
# `fn(q, entry, table, positions)` resolves with no configuration.
DEFAULT_QUANT_ROLES: tp.Mapping[str, str] = {
    "k": "['k']", "v": "['v']",
    "k_scale": "['k_scale']", "v_scale": "['v_scale']",
}


@dataclasses.dataclass
class NumericsProgram:
    """One audited program plus the facts the FT2xx auditors consume.

    Producers fill in whatever they have; each auditor skips programs
    missing its inputs:

    * `jaxpr` — a ClosedJaxpr, OR `fn` + `example_args` to trace one
      here (which also resolves `in_paths`/`out_paths` from the arg /
      output pytrees, keystr-spelled like FT101's leaf paths).
    * `protect_outputs` — output-path substrings naming optimizer /
      loss state: FT202 flags narrowing casts that reach these leaves.
    * `quant_roles` — FT203 input-path substrings for the int8 K/V
      payloads and scales (default matches the paged pool layout);
      FT203 runs only when the scale roles resolve.
    * `seed_fns` — name -> host-side `fn(seed, k)` derivations audited
      by FT204 for the datapipe purity contract (draw k's randomness a
      pure function of (seed, k)).
    * `noqa` — auditor codes suppressed for this program, the numerics
      spelling of the source half's `# flashy: noqa[FT2xx]`.
    """
    label: str
    jaxpr: tp.Any = None
    fn: tp.Optional[tp.Callable] = None
    example_args: tp.Optional[tp.Sequence[tp.Any]] = None
    in_paths: tp.Optional[tp.Sequence[str]] = None
    out_paths: tp.Optional[tp.Sequence[str]] = None
    protect_outputs: tp.Sequence[str] = ()
    quant_roles: tp.Mapping[str, str] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_QUANT_ROLES))
    seed_fns: tp.Mapping[str, tp.Callable] = dataclasses.field(
        default_factory=dict)
    seed_samples: int = 8
    noqa: tp.FrozenSet[str] = frozenset()
    _graph: tp.Optional[ValueGraph] = dataclasses.field(
        default=None, repr=False, compare=False)

    def ensure_traced(self) -> None:
        """Trace `fn(*example_args)` into a jaxpr (plus aligned input /
        output leaf paths) unless a jaxpr was supplied directly."""
        if self.jaxpr is not None or self.fn is None \
                or self.example_args is None:
            return
        import jax
        self.jaxpr, out_shape = jax.make_jaxpr(
            self.fn, return_shape=True)(*self.example_args)
        flat_in, _ = jax.tree_util.tree_flatten_with_path(
            tuple(self.example_args))
        paths = [jax.tree_util.keystr(p) for p, _ in flat_in]
        if len(paths) == len(self.jaxpr.jaxpr.invars):
            self.in_paths = paths
        flat_out, _ = jax.tree_util.tree_flatten_with_path(out_shape)
        out_paths = [jax.tree_util.keystr(p) for p, _ in flat_out]
        if len(out_paths) == len(self.jaxpr.jaxpr.outvars):
            self.out_paths = out_paths

    def graph(self) -> tp.Optional[ValueGraph]:
        self.ensure_traced()
        if self.jaxpr is None:
            return None
        if self._graph is None:
            self._graph = ValueGraph(self.jaxpr)
        return self._graph

    def invars_matching(self, needle: str) -> tp.List[tp.Any]:
        """Top-level jaxpr invars whose arg-tree path contains `needle`
        (empty when paths could not be aligned)."""
        graph = self.graph()
        if graph is None or self.in_paths is None:
            return []
        return [var for path, var in zip(self.in_paths, graph.invars)
                if needle in path]

    def outvars_matching(self, needles: tp.Sequence[str]
                         ) -> tp.Set[tp.Any]:
        graph = self.graph()
        if graph is None or self.out_paths is None:
            return set()
        inner = self.jaxpr.jaxpr
        return {(var, "") for path, var in zip(self.out_paths,
                                               inner.outvars)
                if not _is_literal(var)
                and any(needle in path for needle in needles)}


class NumericsAuditor:
    """Base class mirroring `trace.core.TraceAuditor`: subclasses set
    `code`/`name`/`explain` and implement `audit`. Stateless — one
    instance is reused across programs."""

    code: str = "FT200"
    name: str = "base"
    explain: str = ""

    def audit(self, program: NumericsProgram
              ) -> tp.Iterable[NumericsFinding]:
        raise NotImplementedError
