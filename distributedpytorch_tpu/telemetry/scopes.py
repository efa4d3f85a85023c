"""From a traced device op to the part of the step it belongs to.

A device trace names an event by HLO instruction (``%fusion.2019``); the
module that asked for the work is in that instruction's ``op_name`` metadata
in the *executable's* text (``jit(step_fn)/jvp(DANet)/backbone/layer3_5/
conv2/conv_general_dilated``).  Flax puts every module call in a
``jax.named_scope`` of the module's name; the step adds the scopes below
where no module owns the work.  This module is the one place that joins the
two:

* :func:`scope_table` parses ``compiled.as_text()`` into ``{instruction:
  Scope}``;
* :func:`attribute` applies a table to ``[name, start_ns, end_ns]`` events
  (self time: an op nested in a ``while`` counts once);
* :func:`table_for` makes the table of a jitted step through
  :func:`telemetry.lowering.lower_cached` and detects an executable that the
  persistent compile cache handed back with another tree's metadata;
* :func:`read_device_events` / :func:`summarize_capture` read an
  ``.xplane.pb`` (``jax.profiler.ProfileData`` only) into the summary that
  :class:`telemetry.trace.TraceCapture` writes beside every capture.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

# ----------------------------------------------------------------- vocabulary
#: scopes of the step that no Flax module owns (``parallel/step.py``)
LOSS = "loss"
GRAD_REDUCE = "grad_reduce"
OPTIMIZER = "optimizer"
#: custom-VJP reverse passes and the Mosaic calls (``ops/pallas_attention.py``).
#: A Mosaic call's ``name=`` is also its innermost scope, and the TPU compiler
#: names the custom-call instruction after the innermost scope: the PAM
#: forward stays ``%pam`` (the benchmark's ``pam_kernel_roofline`` holds on to
#: that), the CAM calls become ``%cam_energy`` and ``%cam_apply``.
PAM_KERNEL = "pam"
PAM_BWD = "pam_bwd"
#: the reverse pass's own Mosaic calls, entered under :data:`PAM_BWD`: their
#: names begin with it (``%pam_bwd_fused.1`` ... in the trace), so the
#: forward's pattern ``%pam(.N)? custom-call`` does not match them
PAM_BWD_FUSED = "pam_bwd_fused"
PAM_BWD_DKV = "pam_bwd_dkv"
PAM_BWD_DQ = "pam_bwd_dq"
#: causal grouped-query attention's Mosaic calls (a token model's ``*``
#: layers).  Never ``pam…``: the DANet cells' roofline patterns hold on to
#: that.  No entry in :data:`KERNEL_SCOPE_LAYER` either: the trunk's layer
#: and the prediction module's run the same calls, and each call's layer is
#: the block that its ``op_name`` passes through
CAUSAL_ATTN = "causal_attn"
CAUSAL_ATTN_BWD = "causal_attn_bwd"
CAUSAL_ATTN_BWD_FUSED = "causal_attn_bwd_fused"
CAUSAL_ATTN_BWD_DKV = "causal_attn_bwd_dkv"
CAUSAL_ATTN_BWD_DQ = "causal_attn_bwd_dq"
#: the same kernels under a block-diffusion mask (``models/sdar_lm.py``; the
#: rule ``ops/pallas_attention.py::BlockDiffusion``).  Names of their own:
#: the causal cells' roofline pattern holds on to ``causal_attn…``
BLOCKDIFF_ATTN = "blockdiff_attn"
BLOCKDIFF_ATTN_BWD = "blockdiff_attn_bwd"
BLOCKDIFF_ATTN_BWD_FUSED = "blockdiff_attn_bwd_fused"
BLOCKDIFF_ATTN_BWD_DKV = "blockdiff_attn_bwd_dkv"
BLOCKDIFF_ATTN_BWD_DQ = "blockdiff_attn_bwd_dq"
#: a learned sparse attention's Mosaic calls (``models/keye_lm.py``): the
#: causal kernels given a per-query key set, the head-averaged probabilities
#: its selector is aligned with, the selector's index scores forward and
#: reverse, and the exact top-k selection.  Never ``causal_attn…`` nor
#: ``pam…``: other cells' roofline patterns hold on to those
SPARSE_ATTN = "sparse_attn"
SPARSE_ATTN_BWD = "sparse_attn_bwd"
SPARSE_ATTN_BWD_FUSED = "sparse_attn_bwd_fused"
SPARSE_ATTN_BWD_DKV = "sparse_attn_bwd_dkv"
SPARSE_ATTN_BWD_DQ = "sparse_attn_bwd_dq"
SPARSE_ATTN_PROBS = "sparse_probs"
INDEXER_SCORES = "indexer_scores"
INDEXER_SCORES_BWD = "indexer_scores_bwd"
TOPK_KEEP = "topk_keep"
CAM_BWD = "cam_bwd"
CAM_ENERGY = "cam_energy"
CAM_APPLY = "cam_apply"
#: layers of a token model (``models/nemotron_h.py``): a block runs under its
#: layer's name with its own (``l03``) as the sub-path; the parts of a block
#: that a metric reads alone have a scope each
EMBED = "embed"
MAMBA = "mamba"
ATTN = "attn"
MOE = "moe"
MTP = "mtp"
LM_HEAD = "lm_head"
MAMBA_IN_PROJ = "in_proj"
MAMBA_CONV = "conv"
#: not ``scan``: that is an element JAX itself puts on the name stack
MAMBA_SCAN = "ssd_scan"
MAMBA_OUT_PROJ = "out_proj"
MOE_ROUTER = "router"
MOE_DISPATCH = "dispatch"
MOE_ROUTED_EXPERTS = "routed_experts"
MOE_COMBINE = "combine"
MOE_SHARED_EXPERT = "shared_expert"
MOE_LATENT = "latent"
#: the parts of a learned sparse attention block under ``attn/l<i>/``: the
#: index scores (three products, the ReLU, the sum over heads), the exact
#: top-k selection (threshold and keep set), the alignment target and its KL
ATTN_INDEXER = "indexer"
ATTN_TOPK_SELECT = "topk_select"
ATTN_INDEX_ALIGN = "index_align"
#: the elementwise and grouped mixing of a compressed convolutional
#: attention block under ``attn/l<i>/``: the value shift, both causal
#: convolutions, the q-k mean, the L2 norm and temperature, the half rotary
ATTN_CCA_MIX = "cca_mix"
#: a multi-head latent attention block's latent path under ``attn/l<i>/``:
#: the key/value down-projection, the latent's RMSNorm, the up-projection
#: into every head's keys and values, the rotary parts of q and of the one
#: shared key head, that key head's broadcast and the assembly of q and k
MLA_LATENT = "mla_latent"
#: a dense feed-forward block (a token model's leading dense layer)
MLP = "mlp"
TOKEN_LAYERS = (EMBED, MAMBA, ATTN, MOE, MTP, LM_HEAD)
#: ops of the model that sit in no sub-module (the logits' final upsample)
MODEL = "model"
#: no ``op_name``, or only a parameter's
OTHER = "other"

STEP_SCOPES = (LOSS, GRAD_REDUCE, OPTIMIZER)
#: kernel scopes count under this model layer whether or not the reverse
#: pass's name stack still carries the ``head/pam`` prefix
KERNEL_SCOPE_LAYER = {PAM_KERNEL: "head", PAM_BWD: "head", CAM_BWD: "head",
                      CAM_ENERGY: "head", CAM_APPLY: "head"}
#: host annotations on the profiler's clock: ``GoodputAccountant.account``
#: (``goodput/<bucket>``) and the trainer's dispatch (``StepTraceAnnotation``)
GOODPUT_PREFIX = "goodput/"
STEP_ANNOTATION = "train"
#: the input feed's spans, each with the batch's index in the epoch as its
#: ``batch`` argument: the loader's producer from the first sample of a
#: batch to its collate (not its wait for room in the queue), and the
#: placement thread's wire transform, key filter and ``shard_batch``
INPUT_BATCH = "input/batch"
INPUT_PLACE = "input/place"
#: every name the program itself puts on the profiler's host timeline is the
#: step annotation or starts with one of these: the goodput buckets, the
#: feed, and the ``telemetry.span`` paths of the evaluator, the checkpoint
#: manager, the preemption guard and the consensus primitive.  Declared, not
#: remembered: a reader of a trace holds on to these.
PROGRAM_SPAN_PREFIXES = (GOODPUT_PREFIX, "input/", "eval/", "checkpoint/",
                         "preempt/", "consensus/")


def bucket_scope(k: int) -> str:
    """Inner scope of gradient bucket ``k`` under :data:`GRAD_REDUCE`."""
    return f"b{k}"


@dataclasses.dataclass(frozen=True)
class Scope:
    layer: str
    path: str
    phase: str
    mixed: bool = False
    opcode: str = ""


# ------------------------------------------------------------------- op_names
_TRANSFORM = re.compile(r"^([\w\-]+)\((.*)\)$")   # jvp(..), transpose(..)
#: transforms that wrap a *function's* name, not a scope
_FUNCTION_TRANSFORMS = frozenset({"jit", "pjit", "xla_call", "named_call"})
_SCOPE_NAME = re.compile(r"^[A-Za-z_][\w\-]*$")
#: name-stack elements JAX adds for control flow and calls, not scopes
_STRUCTURAL = frozenset({
    "while", "body", "cond", "scan", "checkpoint", "remat",
    "rematted_computation", "custom_vjp_call", "custom_jvp_call", "pjit",
    "closed_call", "core_call", "shard_map"})
_BRANCH = re.compile(r"^branch_\d+_fun$")


def _split_stack(op_name: str) -> list:
    """``a/b(c/d)/e`` -> ``[a, b(c/d), e]``: split at ``/`` outside parens."""
    out, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return [p for p in out if p]


def _scope_element(element: str) -> str | None:
    """The ``named_scope`` an element of the name stack stands for, if any.
    A scope entered under ``value_and_grad`` reads ``jvp(loss)`` forward and
    ``transpose(jvp(loss))`` backward; ``jit(_where)`` wraps a function's
    name, ``DANet._decode`` is a method, ``bnm,bmc->bnc`` an einsum."""
    while True:
        m = _TRANSFORM.match(element)
        if not m:
            break
        if m.group(1) in _FUNCTION_TRANSFORMS:
            return None
        element = m.group(2)
    if not _SCOPE_NAME.match(element) or element in _STRUCTURAL \
            or _BRANCH.match(element):
        return None
    return element


def is_name_stack(op_name: str) -> bool:
    """Whether ``op_name`` is a name stack (``jit(step_fn)/.../add``; inside
    a not yet inlined call ``grad_reduce/b0/psum``) and not an argument's
    name (``state.params['backbone']...``), which XLA copies onto converts
    and copies of that argument."""
    return "/" in op_name and "[" not in op_name.partition("/")[0]


def module_path(op_name: str) -> tuple[list, bool]:
    """``(path, in_model)`` of one ``op_name``: the chain of scopes with the
    transform wrappers, JAX's structural elements and the primitive at the
    end taken off.  The first scope, unless it is one of the step's own, is
    the model's root module (Flax names it by its class): it goes too, and
    ``in_model`` says it was there."""
    parts = _split_stack(op_name)
    if parts and not _TRANSFORM.match(parts[-1]):
        parts = parts[:-1]  # the primitive
    path = [p for p in map(_scope_element, parts) if p]
    if path and path[0] not in STEP_SCOPES \
            and path[0] not in KERNEL_SCOPE_LAYER:
        # the reverse pass of a rematerialised block re-enters the root
        # inside the block's own scope (``NemotronH/mamba/NemotronH/mamba/
        # l03``): the path starts after the root's last occurrence
        last = len(path) - 1 - path[::-1].index(path[0])
        return path[last + 1:], True
    return path, False


def scope_of(op_name: str | None, opcode: str = "") -> Scope:
    """The scope of one instruction from its own ``op_name`` (the first
    resolvable one where XLA joined several with ``;``; ``mixed`` if their
    layers differ)."""
    scopes = [_scope_of_one(n, opcode) for n in (op_name or "").split(";")
              if n and is_name_stack(n)]
    scopes = [s for s in scopes if s.layer != OTHER]
    if not scopes:
        return Scope(OTHER, "", "fwd", False, opcode)
    mixed = len({s.layer for s in scopes}) > 1
    return dataclasses.replace(scopes[0], mixed=mixed)


def _scope_of_one(op_name: str, opcode: str) -> Scope:
    path, in_model = module_path(op_name)
    if path:
        layer = KERNEL_SCOPE_LAYER.get(path[0], path[0])
    else:
        layer = MODEL if in_model else OTHER
    phase = "opt" if layer == OPTIMIZER else (
        "bwd" if "transpose(" in op_name else "fwd")
    return Scope(layer, "/".join(path), phase, False, opcode)


# ------------------------------------------------------------------- HLO text
_COMP_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations|"
    r"called_computations|true_computation|false_computation)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
_HEAVY = {"convolution": 3, "dot": 3, "custom-call": 3,
          "reduce": 2, "reduce-window": 2, "all-reduce": 2,
          "select-and-scatter": 2, "scatter": 2}


@dataclasses.dataclass
class _Instr:
    name: str
    opcode: str
    op_name: str | None
    called: list
    root: bool
    operands: str


def _operand_text(rhs: str, opcode_end: int) -> str:
    """What stands between the opcode's ``(`` and its matching ``)``."""
    depth = 0
    for i in range(opcode_end, len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                return rhs[opcode_end + 1:i]
    return rhs[opcode_end + 1:]


def parse_hlo(hlo_text: str) -> dict:
    """``{computation: [instructions]}`` of an HLO module's text."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if cur is None or not line.startswith(" "):
            m = _COMP_HEAD.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                continue
            if line.startswith("}"):
                cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rhs = " " + m.group(3)
        op = _OPCODE.search(rhs)
        name = _OP_NAME.search(rhs)
        called = []
        for kind, val in _CALLED.findall(rhs):
            called.extend((kind == "condition", v.strip().lstrip("%"))
                          for v in val.strip("{}").split(",") if v.strip())
        cur.append(_Instr(
            m.group(2), op.group(1) if op else "",
            name.group(1).replace("\\'", "'") if name else None,
            [c for _, c in sorted(called)],  # a loop's condition last
            bool(m.group(1)),
            _operand_text(rhs, op.end() - 1) if op else ""))
    return comps


#: ops that only move or regroup data: where they carry no scope of their own
#: (the compiler's prefetches and layout copies do not) they go to the op
#: that uses what they produce
_MOVERS = frozenset({
    "copy", "copy-start", "copy-done", "slice-start", "slice-done", "bitcast",
    "get-tuple-element", "tuple", "custom-call", "async-start", "async-done",
    "dynamic-slice", "slice", "concatenate", "pad", "transpose", "reshape",
    "convert", "broadcast"})
_NAME_TOKEN = re.compile(r"%?([A-Za-z_][\w.\-]*)")


def scope_table(hlo_text: str) -> dict:
    """``{instruction name: Scope}`` for every instruction of the executable
    that can be a traced event: those of the entry and of called, loop and
    branch computations; not the insides of fusions and reducers, which the
    device runs as one op.  A fusion goes to the heaviest instruction of its
    fused computation and is ``mixed`` where its insides span layers.  A
    data-moving op with no scope of its own goes to its first user that has
    one."""
    comps = parse_hlo(hlo_text)
    inside = set()  # computations run as part of one op
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode == "fusion" or (
                    ins.opcode not in ("while", "call", "conditional",
                                       "async-start") and ins.called):
                inside.update(ins.called)
    memo: dict = {}

    def of_computation(comp: str, seen: tuple) -> Scope | None:
        """Heaviest resolvable instruction of ``comp`` (convolution, dot,
        custom-call; then a reduce; then the root) and whether its
        resolvable instructions span layers."""
        if comp in memo:
            return memo[comp]
        best, best_rank, layers = None, -1, set()
        for ins in comps.get(comp, ()):
            s = resolve(ins, seen + (comp,))
            if s.layer == OTHER:
                continue
            layers.add("+" if s.mixed else s.layer)
            rank = 4 if ins.opcode == "fusion" \
                else _HEAVY.get(ins.opcode, 0) * 2 + ins.root
            if rank > best_rank:
                best, best_rank = s, rank
        if best is not None:
            best = dataclasses.replace(
                best, mixed=len(layers) > 1 or "+" in layers)
        memo[comp] = best
        return best

    def resolve(ins: _Instr, seen: tuple = ()) -> Scope:
        own = scope_of(ins.op_name, ins.opcode)
        if ins.opcode == "fusion" or own.layer == OTHER:
            for comp in ins.called:
                s = None if comp in seen else of_computation(comp, seen)
                if s is not None:
                    return dataclasses.replace(s, opcode=ins.opcode)
        return own

    table = {}
    for comp, instrs in comps.items():
        if comp in inside:
            continue
        local = {ins.name: resolve(ins) for ins in instrs}
        users: dict = {}
        for ins in instrs:
            for tok in _NAME_TOKEN.findall(ins.operands):
                if tok in local and tok != ins.name:
                    users.setdefault(tok, []).append(ins.name)

        def from_users(name, depth=0):
            for u in users.get(name, ()):
                s = local[u]
                if s.layer == OTHER and s.opcode in _MOVERS and depth < 8:
                    s = from_users(u, depth + 1)
                if s is not None and s.layer != OTHER:
                    return s
            return None

        for name, s in local.items():
            if s.layer == OTHER and s.opcode in _MOVERS:
                via = from_users(name)
                if via is not None:
                    local[name] = dataclasses.replace(
                        via, opcode=s.opcode, mixed=False)
        table.update(local)
    return table


def scope_paths(text: str, depth: int = 2) -> set:
    """The ``depth``-element prefixes of every scope path named in an HLO
    text's ``op_name``s — what :func:`table_for` compares between the
    executable and the fresh lowering."""
    out = set()
    for joined in set(_OP_NAME.findall(text)):
        for n in joined.split(";"):
            if n and is_name_stack(n):
                path, _ = module_path(n)
                for d in range(1, min(depth, len(path)) + 1):
                    out.add("/".join(path[:d]))
    return out


# ---------------------------------------------------------------- attribution
def event_instruction(name: str) -> str:
    """The instruction a trace event names: ``%fusion.3 = f32[..] fusion(..)``
    or the benchmark's short form ``%fusion.3 fusion`` -> ``fusion.3``."""
    return name.partition(" ")[0].lstrip("%")


def self_times(events: list) -> dict:
    """Nanoseconds per event name, each instant counted once, for the
    innermost event that covers it."""
    total: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + own

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return total


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")


def is_collective(opcode: str) -> bool:
    """A collective that holds the ops line: the synchronous form or the
    ``-done`` of an async pair (its ``-start`` only issues)."""
    return opcode.endswith("-done") and opcode[:-5] in _COLLECTIVES \
        or opcode in _COLLECTIVES


def attribute(ops: list, table: dict) -> dict:
    """Self time in seconds of ``[name, start_ns, end_ns]`` events by layer,
    by layer and phase, by path; in ``mixed`` fusions; in collectives by
    layer; and of events whose name the table does not hold."""
    by_layer: dict = {}
    by_lp: dict = {}
    by_path: dict = {}
    coll: dict = {}
    mixed = unresolved = busy = 0.0

    def add(d, k, v):
        d[k] = d.get(k, 0.0) + v

    for name, ns in self_times(ops).items():
        t = ns / 1e9
        busy += t
        s = table.get(event_instruction(name))
        if s is None:
            unresolved += t
            continue
        add(by_layer, s.layer, t)
        add(by_lp, f"{s.layer}.{s.phase}", t)
        add(by_path, s.path or s.layer, t)
        if s.mixed:
            mixed += t
        if is_collective(s.opcode):
            add(coll, s.layer, t)
    return {"by_layer": by_layer, "by_layer_phase": by_lp, "by_path": by_path,
            "collective_by_layer": coll, "mixed_s": mixed,
            "unresolved_s": unresolved, "busy_s": busy}


# -------------------------------------------------------------- the step's table
@dataclasses.dataclass
class ScopeTable:
    """A step program's table: ``module`` is the executable's name (what the
    trace's ``XLA Modules`` line calls its runs), ``stale`` says the
    executable carries another tree's scopes (``differing``: the scope paths
    only one of executable and lowering names)."""

    table: dict
    module: str = ""
    stale: bool = False
    recompiled: bool = False
    differing: tuple = ()

    def to_json(self) -> dict:
        return {"module": self.module, "stale": self.stale,
                "recompiled": self.recompiled,
                "differing": list(self.differing),
                "columns": ["layer", "path", "phase", "mixed", "opcode"],
                "instructions": {k: list(dataclasses.astuple(v))
                                 for k, v in self.table.items()}}

    @classmethod
    def from_json(cls, doc: dict) -> "ScopeTable":
        return cls({k: Scope(*v) for k, v in doc["instructions"].items()},
                   module=doc.get("module", ""),
                   stale=doc.get("stale", False),
                   recompiled=doc.get("recompiled", False),
                   differing=tuple(doc.get("differing", ())))


_MODULE_NAME = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def _compile_uncached(lowered):
    """Compile ``lowered`` afresh: a new executable with this tree's
    metadata.  The persistent cache is off for this one call (no read, and
    no new entry to push another out), and a dump option that changes no
    code makes the call differ from the one JAX memoizes in the process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile(
            compiler_options={"xla_dump_max_hlo_modules": 1})
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def differing_paths(executable_text: str, lowering_text: str) -> tuple:
    """The scope paths (two levels) that only one of an executable and a
    lowering names, less what the compiler may rightly have deleted: over a
    one-device ``data`` axis the ``grad_reduce`` collectives reduce nothing
    and nothing of them reaches the executable."""
    have, want = scope_paths(executable_text), scope_paths(lowering_text)
    deleted = {p for p in want - have if p.split("/")[0] == GRAD_REDUCE}
    return tuple(sorted((want ^ have) - deleted))


def table_for(jitted, *args, allow_recompile: bool = False) -> ScopeTable:
    """The scope table of ``jitted`` at ``args`` (arrays or
    ``ShapeDtypeStruct``s), through the process-wide lowering cache: the
    lowering and the executable are the ones the MFU estimate already made.

    JAX leaves metadata out of the persistent cache's key, so an executable
    loaded from it carries the ``op_name``s of whichever tree compiled it
    first.  The fresh lowering is in hand: where the executable's scope paths
    and the lowering's differ (:func:`differing_paths`), the executable is
    from another tree — compile once more past the cache
    (``allow_recompile``; instruction names are the same, metadata steers no
    pass), or hand the table back marked ``stale``."""
    from .lowering import lower_cached

    prog = lower_cached(jitted, *args)
    text = prog.compiled.as_text()
    lowering = prog.lowered.as_text(dialect="hlo", debug_info=True)
    differing = differing_paths(text, lowering)
    recompiled = False
    if differing and allow_recompile:
        text = _compile_uncached(prog.lowered).as_text()
        differing = differing_paths(text, lowering)
        recompiled = True
    module = _MODULE_NAME.search(text)
    return ScopeTable(scope_table(text),
                      module=module.group(1) if module else "",
                      stale=bool(differing) and not recompiled,
                      recompiled=recompiled, differing=differing)


# ------------------------------------------------------------- captured traces
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def read_device_events(trace_dir: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}`` of the newest ``.xplane.pb`` under ``trace_dir``, device events
    as ``[name, start_ns, end_ns]``, host events with their thread (the
    plane's line) as a fourth element."""
    import jax

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns,
                                 e.start_ns + e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                out["host"].extend(
                    [e.name, e.start_ns, e.start_ns + e.duration_ns, i]
                    for e in line.events if e.duration_ns > 0)
    return out


def program_spans(host: list) -> list:
    """The host events that the program itself put on the profiler's clock
    (the train step and the declared :data:`PROGRAM_SPAN_PREFIXES`), apart
    from the profiler's own."""
    return [h for h in host if h[0] == STEP_ANNOTATION
            or h[0].startswith(PROGRAM_SPAN_PREFIXES)]


def loop_thread_spans(host: list) -> list:
    """Of ``host`` (events with their thread as fourth element), those on
    the thread that dispatched the train steps.  A device gap is the loop's
    to explain: a feed worker's span that happens to run across it says
    nothing about why the chip waited.  Events that carry no thread, or a
    trace with no step in it, come back whole."""
    loop = {h[3] for h in host if h[0] == STEP_ANNOTATION and len(h) > 3}
    if not loop:
        return host
    return [h for h in host if len(h) > 3 and h[3] in loop]


def idle_gaps(ops: list, host: list, top: int = 10) -> tuple[list, dict]:
    """``(longest, by_span)``: the ``top`` longest gaps between device ops,
    each named by the innermost of ``host``'s spans that covers its middle,
    and the idle seconds of ALL gaps summed per such name."""
    gaps, cur_e = [], None
    for _, s, e in sorted(ops, key=lambda ev: ev[1]):
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    # one sweep over gaps and spans in time order: a whole epoch's trace
    # holds a gap between most pairs of ops
    spans = sorted(host, key=lambda h: h[1])
    i, active, named, by_span = 0, [], [], {}
    for s, e in sorted(gaps):
        mid = (s + e) / 2
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[2] >= mid]
        name = min(active, key=lambda h: h[2] - h[1])[0] if active \
            else "no host span"
        named.append([name, (e - s) / 1e9])
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
    named.sort(key=lambda g: -g[1])
    return named[:top], by_span


def summarize_capture(raw: dict, table: ScopeTable) -> dict:
    """``scope_summary.json`` of one capture: per device, the executions of
    the table's step program and the ops inside them attributed by it, in
    milliseconds per step; the program's own host spans in the trace, and the
    first device's idle gaps (the longest, and the total per name) by the
    innermost of those on the loop's thread."""
    rx = re.compile("^" + re.escape(table.module))
    devices = []  # (steps, ops inside them, their attribution)
    for plane in sorted(raw["devices"]):
        dev = raw["devices"][plane]
        runs = [m for m in dev["modules"] if rx.search(m[0])]
        if not runs or not dev["ops"]:
            continue
        t0, t1 = min(r[1] for r in runs), max(r[2] for r in runs)
        ops = [[n, max(s, t0), min(e, t1)] for n, s, e in dev["ops"]
               if e > t0 and s < t1]
        devices.append((len(runs), ops, attribute(ops, table.table)))
    out = {"stale": table.stale, "recompiled": table.recompiled,
           "devices": len(devices), "steps": 0}
    if not devices:
        return out

    def per_step_ms(key):
        """Mean over devices of ``attribution[key]`` per step, in ms."""
        sums: dict = {}
        for steps, _, a in devices:
            for k, v in a[key].items():
                sums[k] = sums.get(k, 0.0) + 1e3 * v / steps / len(devices)
        return dict(sorted(sums.items()))

    def share(key):
        busy = sum(a["busy_s"] for _, _, a in devices)
        return sum(a[key] for _, _, a in devices) / busy if busy else 0.0

    steps0, ops0, _ = devices[0]
    host = program_spans(raw["host"])
    longest, idle_by_span = idle_gaps(ops0, loop_thread_spans(host))
    out.update({
        "steps": steps0,
        "busy_ms_per_step": sum(1e3 * a["busy_s"] / steps
                                for steps, _, a in devices) / len(devices),
        "ms_per_step_by_layer": per_step_ms("by_layer"),
        "ms_per_step_by_layer_phase": per_step_ms("by_layer_phase"),
        "ms_per_step_collectives_by_layer": per_step_ms("collective_by_layer"),
        "heaviest_paths_ms_per_step": sorted(
            per_step_ms("by_path").items(), key=lambda kv: -kv[1])[:20],
        "mixed_share": share("mixed_s"),
        "unresolved_share": share("unresolved_s"),
        "idle_gaps": longest,
        "idle_by_span_s": idle_by_span,
        "host_spans": {name: sum(1 for h in host if h[0] == name)
                       for name in sorted({h[0] for h in host})},
    })
    return out
