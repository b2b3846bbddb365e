"""Layer 1: static invariant checks on lowered round programs.

Four invariants, each of which the runtime equivalence tests can only
witness indirectly, are proven on the program text itself:

1. **No float64.**  The attack/selection arithmetic is an f32 lane; a bare
   Python literal in a ``jnp.where`` promotes to a weak f64 scalar the
   moment anyone enables x64.  The auditor retraces every entry body under
   ``jax.enable_x64(True)`` — f32 example inputs stay f32, so any
   float64 dtype in the retraced jaxpr is a latent weak-type leak.
2. **No host callbacks.**  ``pure_callback`` / ``io_callback`` /
   ``debug_callback`` inside a device round serializes the round on host
   round-trips; the jaxpr must not contain the callback primitives and the
   compiled HLO must not contain callback custom-calls or channel ops
   (:func:`repro.launch.hlo_analysis.host_transfer_counts`).
3. **Donation applied.**  ``donate_argnums`` is intent; the proof is the
   lowered module's ``tf.aliasing_output`` attributes and the compiled
   executable's ``input_output_alias`` header.  Each donated entry must
   alias exactly one output per theta-carry leaf.
4. **One stacked fetch.**  The only non-aliased outputs of a device round
   are the stacked fetch leaves; their count is pinned per entry
   (accept -> 1, round -> 2, sweep -> 3, ...).

Every check returns :class:`~repro.analysis.findings.Finding` objects so
the CLI/CI layer treats program violations and lint hits uniformly.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from ..launch.hlo_analysis import host_transfer_counts
from .findings import Finding, make_finding

# jaxpr primitives that re-enter the host mid-program
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "debug_print", "callback")

_ALIAS_PAIR_RE = re.compile(r"\{(\d+)\}:\s*\((\d+),")


def _balanced_region(text: str, key: str) -> Optional[str]:
    """Contents of the brace block opened by ``key`` (which ends in ``{``),
    matched by brace depth — the block nests shape braces like ``{0}``."""
    i = text.find(key)
    if i < 0:
        return None
    start = i + len(key)
    depth = 1
    for j in range(start, len(text)):
        c = text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return text[start:j]
    return None


def iter_eqns(jaxpr):
    """Every equation of a (closed) jaxpr, descending into sub-jaxprs held
    in equation params (scan/while/cond bodies, custom_vjp calls, ...)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from iter_eqns(sub)


def find_dtypes(jaxpr, bad: Sequence[str] = ("float64",)) -> List[Tuple[str, str]]:
    """(primitive, dtype) pairs for every eqn touching a forbidden dtype."""
    bad = tuple(bad)
    hits: List[Tuple[str, str]] = []
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) in bad:
                hits.append((eqn.primitive.name, str(dtype)))
    return hits


def find_callbacks(jaxpr) -> List[str]:
    """Names of host-callback primitives appearing anywhere in the jaxpr."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in CALLBACK_PRIMITIVES]


def lowered_alias_count(lowered_text: str) -> int:
    """Donated-input markers in the lowered StableHLO (pre-compile intent).
    Single-device lowerings pin the pairing as ``tf.aliasing_output``;
    multi-device (sharded) lowerings mark ``jax.buffer_donor`` and leave the
    pairing to XLA — both prove the donation survived lowering."""
    return (lowered_text.count("tf.aliasing_output")
            + lowered_text.count("jax.buffer_donor"))


def compiled_alias_pairs(compiled_text: str) -> List[Tuple[int, int]]:
    """(output_index, input_index) pairs from the compiled executable's
    ``input_output_alias`` header — donation as actually applied."""
    body = _balanced_region(compiled_text, "input_output_alias={")
    if body is None:
        return []
    return [(int(o), int(i)) for o, i in _ALIAS_PAIR_RE.findall(body)]


def entry_output_arity(compiled_text: str) -> Optional[int]:
    """Number of entry outputs, from the entry_computation_layout header."""
    m = re.search(r"entry_computation_layout=.*?->\s*(\([^)]*\)|[^,]+?)\}",
                  compiled_text, re.DOTALL)
    if not m:
        return None
    body = m.group(1)
    if body.startswith("("):
        inner = body[1:-1] if body.endswith(")") else body[1:]
        if not inner.strip():
            return 0
        # arity = top-level comma count + 1 (shapes contain bracketed commas)
        depth, count = 0, 1
        for c in inner:
            if c in "([{":
                depth += 1
            elif c in ")]}":
                depth -= 1
            elif c == "," and depth == 0:
                count += 1
        return count
    return 1


@dataclasses.dataclass
class ProgramAudit:
    """Everything the auditor measured about one program cell."""
    name: str
    findings: List[Finding]
    eqns: int = 0
    donated_inputs: int = 0
    aliased_outputs: int = 0
    outputs: int = 0
    fetch_leaves: int = 0
    transfers: Dict[str, int] = dataclasses.field(default_factory=dict)

    def budget_row(self) -> Dict[str, Any]:
        """The numbers pinned in ``analysis/budgets/programs.json``."""
        return {
            "eqns": self.eqns,
            "donated_inputs": self.donated_inputs,
            "aliased_outputs": self.aliased_outputs,
            "outputs": self.outputs,
            "fetch_leaves": self.fetch_leaves,
            "outfeed": self.transfers.get("outfeed", 0),
            "infeed": self.transfers.get("infeed", 0),
            "send": self.transfers.get("send", 0),
            "recv": self.transfers.get("recv", 0),
            "host_callback": self.transfers.get("host_callback", 0),
            "custom_call": self.transfers.get("custom_call", 0),
        }


def audit_fn(fn: Callable, args: tuple, *, name: str,
             donate_argnums: Tuple[int, ...] = (),
             expected_donated: int = 0,
             expected_fetch_leaves: Optional[int] = None,
             x64_retrace: bool = True,
             compile_program: bool = True,
             lowered=None) -> ProgramAudit:
    """Audit one jittable callable against the four invariants.

    ``fn`` is the *un-jitted* body (e.g. ``RoundRunner.audit_body(which)``);
    ``expected_donated`` is the number of carry leaves that must alias
    (0 for non-donated entries); ``expected_fetch_leaves`` pins the
    non-aliased output count when given.  Pass ``lowered`` (e.g. from
    ``RoundRunner.lower``) to audit the driver's own program object instead
    of re-lowering a fresh jit of ``fn``.
    """
    findings: List[Finding] = []
    path = f"program:{name}"

    jx = jax.make_jaxpr(fn)(*args)
    eqns = sum(1 for _ in iter_eqns(jx))

    for prim, dtype in find_dtypes(jx):
        findings.append(make_finding(
            "f64-in-program", "error", path, 0,
            f"{dtype} value flows through '{prim}' in the traced program",
            context=f"{name}:{prim}"))
    if x64_retrace:
        with jax.enable_x64(True):
            jx64 = jax.make_jaxpr(fn)(*args)
        for prim, dtype in find_dtypes(jx64):
            findings.append(make_finding(
                "f64-in-program", "error", path, 0,
                f"weak-type promotion: '{prim}' becomes {dtype} under x64 "
                f"(pin the literal to jnp.float32)",
                context=f"{name}:x64:{prim}"))

    for prim in find_callbacks(jx):
        findings.append(make_finding(
            "host-callback-in-program", "error", path, 0,
            f"host callback primitive '{prim}' inside the device program",
            context=f"{name}:{prim}"))

    if lowered is None:
        lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    lowered_aliases = lowered_alias_count(lowered.as_text())
    if lowered_aliases != expected_donated:
        findings.append(make_finding(
            "donation-mismatch", "error", path, 0,
            f"lowered program aliases {lowered_aliases} inputs, expected "
            f"{expected_donated} (theta carry leaves)",
            context=f"{name}:lowered"))

    audit = ProgramAudit(name=name, findings=findings, eqns=eqns,
                         donated_inputs=lowered_aliases)
    if not compile_program:
        return audit

    compiled = lowered.compile()
    ctext = compiled.as_text()
    pairs = compiled_alias_pairs(ctext)
    outputs = entry_output_arity(ctext)
    audit.aliased_outputs = len(pairs)
    audit.outputs = outputs if outputs is not None else -1
    if len(pairs) != expected_donated:
        findings.append(make_finding(
            "donation-mismatch", "error", path, 0,
            f"compiled executable aliases {len(pairs)} outputs, expected "
            f"{expected_donated}",
            context=f"{name}:compiled"))
    if outputs is not None:
        audit.fetch_leaves = outputs - len(pairs)
        if (expected_fetch_leaves is not None
                and audit.fetch_leaves != expected_fetch_leaves):
            findings.append(make_finding(
                "fetch-contract", "error", path, 0,
                f"{audit.fetch_leaves} non-aliased outputs, contract pins "
                f"{expected_fetch_leaves} stacked fetch leaves",
                context=f"{name}:fetch"))

    audit.transfers = host_transfer_counts(ctext)
    for op in ("outfeed", "infeed", "send", "recv", "host_callback"):
        if audit.transfers.get(op, 0):
            findings.append(make_finding(
                "host-transfer-in-program", "error", path, 0,
                f"{audit.transfers[op]} '{op}' op(s) in the compiled round "
                f"program — data may only leave through the stacked fetch",
                context=f"{name}:{op}"))
    return audit
