"""Jaxpr-walking layer shared by every contract check.

Everything the analyzer proves is read off the traced program — never by
executing it.  This module turns a (Closed)Jaxpr into:

  * :class:`PallasSite` records — every ``pallas_call`` equation, with its
    control-flow context (inside a while body or not), grid, operand/result
    avals, scalar-prefetch split and ``input_output_aliases`` — the raw
    material for the census, donation, transfer and ref-hazard passes,
  * per-kernel ref access summaries (:func:`ref_access_counts`,
    :func:`ref_events`): every ``get``/``swap`` on a kernel operand ref —
    and every ``dma_start``, a read of its source and a write of its
    destination — in program order, classified static vs dynamic by
    recovering the ``NDIndexer`` the Pallas tracer flattened into the
    equation.  Accesses
    inside sub-jaxprs (``pl.when`` conds, inner loops) are attributed to the
    outer kernel ref through an invar environment.
  * collective-primitive shapes (:func:`collective_link_bytes`) with the
    same wire weights as ``utils.hlo.collective_bytes`` — the jaxpr-level
    counterpart used where partitioned HLO is unavailable (AbstractMesh
    traces).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
from jax.extend.core import Literal


def _unwrap(jaxpr):
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _aval(var):
    av = var.aval
    return getattr(av, "inner_aval", av)


def _is_literal(x) -> bool:
    return isinstance(x, Literal) or not hasattr(x, "aval")


@dataclass
class RefEvent:
    """One ``get``/``swap`` on a kernel operand ref, in program order."""
    kind: str                    # "get" | "swap"
    order: int                   # DFS program-order index within the kernel
    dynamic: bool                # any traced (non-static) index component
    scatter: bool                # advanced (array-valued) indexing
    indexer: Any = None          # recovered NDIndexer tuple, or None


@dataclass
class PallasSite:
    """One ``pallas_call`` equation with its analysis-relevant structure."""
    name: str                    # the pallas_call's name= (a kernel function
                                 # name where it has none)
    src: str                     # "<name> at <kernel file>:<line>"
    grid: Tuple[int, ...]
    in_while: bool               # inside any while-loop body
    num_scalars: int             # scalar-prefetch operands (index space head)
    num_inputs: int              # non-scalar inputs
    num_outputs: int
    in_avals: List[Any]          # ALL operand avals (scalars first)
    out_avals: List[Any]
    aliases: Dict[int, int]      # absolute operand index -> output index
    eqn: Any = field(repr=False, default=None)

    @property
    def kernel_jaxpr(self):
        return _unwrap(self.eqn.params["jaxpr"])

    def operand_aval(self, idx: int):
        return self.in_avals[idx]

    def root_of_operand(self, idx: int) -> int:
        """Kernel-invar index of operand ``idx`` (identity: scalars lead)."""
        return idx

    def root_of_output(self, j: int) -> int:
        return self.num_scalars + self.num_inputs + j

    def classify_root(self, root: int) -> Tuple[str, int]:
        if root < self.num_scalars:
            return ("scalar", root)
        if root < self.num_scalars + self.num_inputs:
            return ("input", root)               # == absolute operand index
        j = root - self.num_scalars - self.num_inputs
        if j < self.num_outputs:
            return ("output", j)
        return ("scratch", j - self.num_outputs)

    def block_mappings(self):
        return tuple(self.eqn.params["grid_mapping"].block_mappings)

    def output_in_hbm(self, j: int) -> bool:
        """Output ``j`` stays in HBM as a whole (``memory_space=ANY``): the
        kernel moves it by DMA — the ping-pong buffer layout."""
        bm = self.block_mappings()[self.num_inputs + j]
        return str(getattr(bm.block_aval, "memory_space", "")) == "any"


def _sub_jaxprs_with_env(eqn):
    """Yield (sub_jaxpr, operand_list) pairs mapping sub invars to outer vars.

    The operand list aligns positionally with the sub-jaxpr's invars;
    entries may be ``None`` where no outer var corresponds (e.g. consts).
    Handles the primitives that appear inside Pallas kernel bodies: ``cond``
    (operands follow the predicate), ``while`` (cond consts, body consts,
    carry), ``run_scoped`` (outer refs bind the body's constvars, then its
    scoped allocations), ``scan``/``pjit``/``closed_call`` (1:1), with a zip
    fallback.
    """
    name = eqn.primitive.name
    params = eqn.params
    if name == "run_scoped":
        # the body closes over the outer refs as constvars; its invars are
        # the scoped allocations (no outer counterpart)
        sub = _unwrap(params["jaxpr"])
        yield sub, list(eqn.invars) + [None] * len(sub.invars)
        return
    if name == "cond":
        ops = list(eqn.invars[1:])
        for br in params["branches"]:
            yield _unwrap(br), ops
        return
    if name == "while":
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        carry = list(eqn.invars[cn + bn:])
        yield _unwrap(params["cond_jaxpr"]), list(eqn.invars[:cn]) + carry
        yield _unwrap(params["body_jaxpr"]), \
            list(eqn.invars[cn:cn + bn]) + carry
        return
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if not hasattr(x, "eqns"):
                continue
            sub = _unwrap(x)          # ClosedJaxpr proxies .eqns, not .invars
            ops = list(eqn.invars)
            if len(sub.invars) != len(ops):
                ops = [None] * len(sub.invars)   # conservative: untracked
            yield sub, ops


def collect_pallas_sites(jaxpr, _in_while: bool = False) -> List[PallasSite]:
    """Every ``pallas_call`` site in trace order, tagged with while context."""
    jaxpr = _unwrap(jaxpr)
    out: List[PallasSite] = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            gm = eqn.params["grid_mapping"]
            dbg = getattr(eqn.params["jaxpr"], "debug_info", None)
            nsi = str(getattr(dbg, "func_src_info", "") or "")
            raw = eqn.params.get("input_output_aliases", ())
            out.append(PallasSite(
                name=nsi.split(" at ")[0] if nsi else "<pallas>",
                src=nsi,
                grid=tuple(int(g) for g in gm.grid),
                in_while=_in_while,
                num_scalars=int(gm.num_index_operands),
                num_inputs=int(gm.num_inputs),
                num_outputs=int(gm.num_outputs),
                in_avals=[_aval(v) for v in eqn.invars],
                out_avals=[_aval(v) for v in eqn.outvars],
                aliases={int(i): int(o) for i, o in raw},
                eqn=eqn,
            ))
            continue
        inside = _in_while or eqn.primitive.name == "while"
        for sub, _ in _sub_jaxprs_with_env(eqn):
            out.extend(collect_pallas_sites(sub, inside))
    return out


def _recover_indexers(eqn):
    """Unflatten the NDIndexer(s) of a get/swap eqn; None if unavailable."""
    tree = eqn.params.get("tree")
    if tree is None:
        return None
    skip = 1 if eqn.primitive.name == "get" else 2   # get: (ref,); swap: (ref, val)
    try:
        idx = jax.tree_util.tree_unflatten(tree, eqn.invars[skip:])
    except Exception:
        return None
    flat = idx if isinstance(idx, (tuple, list)) else (idx,)
    return tuple(x for x in flat if hasattr(x, "indices"))


def _indexer_dynamics(eqn, indexers) -> Tuple[bool, bool]:
    """(dynamic, scatter) classification of a get/swap's index arguments."""
    if indexers is not None:
        dynamic = scatter = False
        for nd in indexers:
            for comp in nd.indices:
                if hasattr(comp, "start"):            # Slice
                    if not _is_literal(comp.start):
                        dynamic = True
                elif _is_literal(comp):
                    continue
                else:                                  # traced index
                    dynamic = True
                    shape = getattr(_safe_aval(comp), "shape", ())
                    if shape:
                        scatter = True
            if getattr(nd, "int_indexer_shape", ()):
                scatter = True
        return dynamic, scatter
    skip = 1 if eqn.primitive.name == "get" else 2
    extra = eqn.invars[skip:]
    dyn = any(not _is_literal(v) for v in extra)
    scat = any(not _is_literal(v) and
               getattr(_safe_aval(v), "shape", ()) for v in extra)
    return dyn, scat


def _safe_aval(x):
    try:
        return x.aval
    except Exception:
        return None


def _dma_operands(eqn):
    """[("get", src_ref, src_indexers), ("swap", dst_ref, dst_indexers)] of
    a ``dma_start`` equation (its flattened operand tree leads with the
    source ref and transforms, then the destination's)."""
    ops = jax.tree_util.tree_unflatten(eqn.params["tree"], eqn.invars)
    out = []
    for kind, ref, tr in (("get", ops[0], ops[1]), ("swap", ops[2], ops[3])):
        tr = tr if isinstance(tr, (tuple, list)) else (tr,)
        out.append((kind, ref, tuple(t for t in tr if hasattr(t, "indices"))))
    return out


def ref_events(kernel_jaxpr) -> Dict[int, List[RefEvent]]:
    """Program-ordered get/swap events per kernel operand-ref index.

    Events inside conditionals count unconditionally (a hazard behind a
    predicate is still a hazard); inner-jaxpr refs are mapped back to the
    outer kernel invars they alias via the invar environment.
    """
    kernel_jaxpr = _unwrap(kernel_jaxpr)
    events: Dict[int, List[RefEvent]] = {}
    counter = [0]

    def walk(j, env):
        for eqn in j.eqns:
            if eqn.primitive.name == "dma_start":
                # a DMA reads its source ref and writes its destination ref
                counter[0] += 1
                for kind, ref, tr in _dma_operands(eqn):
                    root = env.get(id(ref))
                    if root is not None:
                        dyn = any(not _is_literal(getattr(c, "start", c))
                                  for nd in tr for c in nd.indices)
                        events.setdefault(root, []).append(RefEvent(
                            kind=kind, order=counter[0], dynamic=dyn,
                            scatter=False, indexer=tr))
                continue
            if eqn.primitive.name in ("get", "swap"):
                root = env.get(id(eqn.invars[0]))
                counter[0] += 1
                if root is not None:
                    indexers = _recover_indexers(eqn)
                    dyn, scat = _indexer_dynamics(eqn, indexers)
                    events.setdefault(root, []).append(RefEvent(
                        kind=eqn.primitive.name, order=counter[0],
                        dynamic=dyn, scatter=scat, indexer=indexers))
                continue
            for sub, ops in _sub_jaxprs_with_env(eqn):
                sub_env = {}
                binders = (list(sub.constvars) + list(sub.invars)
                           if eqn.primitive.name == "run_scoped"
                           else sub.invars)
                for iv, ov in zip(binders, ops):
                    if ov is not None and id(ov) in env:
                        sub_env[id(iv)] = env[id(ov)]
                if sub_env:
                    walk(sub, sub_env)

    walk(kernel_jaxpr,
         {id(v): i for i, v in enumerate(kernel_jaxpr.invars)})
    return events


def ref_access_counts(kernel_jaxpr) -> Dict[int, Tuple[int, int]]:
    """{operand-ref index: (num_gets, num_swaps)} for a kernel body."""
    out = {}
    for root, evs in ref_events(kernel_jaxpr).items():
        gets = sum(1 for e in evs if e.kind == "get")
        swaps = sum(1 for e in evs if e.kind == "swap")
        out[root] = (gets, swaps)
    return out


# ----- sort primitive census (jaxpr-level sort-free certification) ----------

def sort_primitive_count(jaxpr) -> int:
    """Recursive count of ``sort`` primitives (jnp.sort/argsort/lexsort),
    including inside Pallas kernel bodies and control-flow sub-jaxprs."""
    jaxpr = _unwrap(jaxpr)
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            total += 1
        for sub, _ in _sub_jaxprs_with_env(eqn):
            total += sort_primitive_count(sub)
    return total


# ----- collective accounting at jaxpr level (AbstractMesh traces) -----------

# wire-byte weights mirror utils.hlo.collective_bytes: sizes are the
# per-device result bytes, P the exchange width.
def collective_link_bytes(jaxpr, num_devices: int):
    """(per-kind wire bytes, per-kind site counts) from jaxpr collectives.

    Shard-map / AbstractMesh traces never reach partitioned HLO on this
    container, so the link accounting reads the collective *primitives*
    instead: ``all_to_all`` / ``all_gather`` wire ``out·(P−1)/P``, ``psum``
    (all-reduce) ``2·out·(P−1)/P``, ``ppermute`` ``out`` — per site, counted
    once per trace site (cond-guarded retry attempts each count once, the
    executed-vs-nominal convention of the launch census).
    """
    p = max(int(num_devices), 1)
    frac = (p - 1) / p
    bytes_by: Dict[str, float] = {}
    counts: Dict[str, int] = {}

    def nbytes(var):
        av = _aval(var)
        size = 1
        for d in av.shape:
            size *= int(d)
        return size * av.dtype.itemsize

    def walk(j):
        j = _unwrap(j)
        for eqn in j.eqns:
            nm = eqn.primitive.name
            if nm in ("all_to_all", "all_gather"):
                wire = sum(nbytes(v) for v in eqn.outvars) * frac
            elif nm in ("psum", "psum2"):
                wire = 2 * sum(nbytes(v) for v in eqn.outvars) * frac
            elif nm in ("psum_scatter", "reduce_scatter"):
                wire = sum(nbytes(v) for v in eqn.invars) * p * frac
            elif nm == "ppermute":
                wire = float(sum(nbytes(v) for v in eqn.outvars))
            else:
                for sub, _ in _sub_jaxprs_with_env(eqn):
                    walk(sub)
                continue
            bytes_by[nm] = bytes_by.get(nm, 0.0) + wire
            counts[nm] = counts.get(nm, 0) + 1

    walk(jaxpr)
    bytes_by["total"] = sum(bytes_by.values())
    return bytes_by, counts
