"""The layers of the fused train step, by name, on the device.

One vocabulary of ``jax.named_scope`` names, planted at each layer
boundary of the step program (``core/env.py``, ``train/ppo.py``,
``train/policies.py``; ``train/impala.py`` plants the two phases) and read
back from the compiled executable's text.  A device trace does NOT carry
the op path (PR 24: an ``XLA Ops`` event is named by its instruction text
without ``metadata={...}``), the optimized HLO does: ``op_name=
"jit(_train_step_impl)/update/while/body/loss/transpose(jvp(policy_forward))/
vmap(MLPPolicy)/Dense_0/dot_general"``.  :func:`scope_map_from_hlo` turns
that text into ``{instruction name: OpScope}``, the join key for a trace's
events; :func:`last_step_scope_map` gives the map of the newest step
program ``bench_util.compile_train_step`` handed out.

``named_scope`` is metadata of the trace only: the compiled program and
its numerics do not change (tests/test_scopes.py pins both).

The scope paths, under ``jit(...)``:

  rollout/policy_act            policy forward, sampling, the chosen action's
                                log-probability (a select over the actions:
                                train/common.picked_logp, no gather)
  rollout/env_step/tape_read    the tape's columns read by bar index
  rollout/env_step/dynamics     action coercion, event overlay, fills and
                                brackets, financing, margin, mark, reward,
                                termination (env-dynamics kernels A and B)
  rollout/env_step/obs          obs window update, build_obs, encode
  rollout/auto_reset            masked resets and the trajectory's casts
  update/gae                    advantages and returns
  update/minibatch_take         permutation, slice and gather of a minibatch
  update/loss                   value_and_grad of the loss; the policy call
  update/loss/policy_forward    inside it, wrapped ``jvp(...)`` forward and
                                ``transpose(jvp(...))`` backward
  update/optimizer              optimizer update, apply, the guard's select
  update/guard                  metrics, quarantine, masked resets
  .../attention, .../ffn        the two halves of a transformer block, under
                                ``policy_act`` and ``policy_forward`` (the
                                decoder trunk: latent or grouped-query
                                attention; the DENSE layer's gated feed-forward)
  .../linear_attention          the Kimi-Delta-Attention half of a hybrid
                                trunk's block, flat under the same two: norm,
                                projections, short convolution, gates, the
                                chunked scan, gated norm, output product
  .../short_conv                the gated-short-convolution half of a block
                                whose kind is ``conv``, flat under the same
                                two: norm, the one projection to B | C | u,
                                the gates, the causal taps, the output product
  .../moe_router                an expert layer, flat under the same two:
                                its norm, the scores, top-k and weights
  .../moe_dispatch              sort by expert, rows to the buffer and back,
                                the weighted sum
  .../moe_experts               grouped products over the experts held
  .../moe_shared                the shared expert (none where a model has none)

The PARTS of a block (PR 37), names the layer vocabulary leaves out
(``PART_NAMES``), so that planting them moves no layer path and no metric
that reads one; only the part map (``part_map_from_hlo``,
``last_step_part_map``) reads them, as an extension of the op's layer path:

  .../linear_attention/kda_scan the whole gated-delta-rule scan, both
                                directions (ops/kda_chunk_scan.py: the loop
                                over windows, the chunks' parts, the inverse,
                                the walk, the scan's own backward pass); not
                                the projections, taps, norms, output product
  .../linear_attention/causal_conv, .../short_conv/causal_conv
                                the pad and the shifted multiply-adds of a
                                short causal convolution; not the gates, SiLU
                                or the projections
  .../attention/attention_core  between the q / k / v products' outputs and
                                the output product's input: head norms, RoPE,
                                the k | v split, the layout (value pad, the
                                repeat to the query heads, lane packing), the
                                attention call; not the pre-norm, the
                                products, the output gate

The part map also names what XLA adds without metadata, by one rule: an
async ``*-done`` takes the scope its users share, its ``*-start`` the
done's, a ``copy`` its user's; a done with no named user stays unnamed.
"""
from __future__ import annotations

import re
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

ROLLOUT = "rollout"
UPDATE = "update"
POLICY_ACT = "policy_act"
ENV_STEP = "env_step"
TAPE_READ = "tape_read"
DYNAMICS = "dynamics"
OBS = "obs"
AUTO_RESET = "auto_reset"
GAE = "gae"
MINIBATCH_TAKE = "minibatch_take"
LOSS = "loss"
POLICY_FORWARD = "policy_forward"
OPTIMIZER = "optimizer"
GUARD = "guard"
ATTENTION = "attention"
FFN = "ffn"
LINEAR_ATTENTION = "linear_attention"
SHORT_CONV = "short_conv"
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
# the parts of an expert layer (train/mla_moe_decoder.py)
MOE_SCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED)

# the parts of a block (PR 37): sub-layer scopes that the LAYER vocabulary
# leaves out, so that no layer path changes; only the part map reads them
KDA_SCAN = "kda_scan"
CAUSAL_CONV = "causal_conv"
ATTENTION_CORE = "attention_core"
PART_NAMES = (KDA_SCAN, CAUSAL_CONV, ATTENTION_CORE)

# the two phases every trainer's fused step plants (PR 6)
PHASE_SCOPES = (ROLLOUT, UPDATE)
SCOPE_NAMES = PHASE_SCOPES + (
    POLICY_ACT, ENV_STEP, TAPE_READ, DYNAMICS, OBS, AUTO_RESET, GAE,
    MINIBATCH_TAKE, LOSS, POLICY_FORWARD, OPTIMIZER, GUARD, ATTENTION, FFN,
) + MOE_SCOPES + (LINEAR_ATTENTION, SHORT_CONV)


def join(*names: str) -> str:
    return "/".join(names)


# the layers of the PPO step, as paths: where the work happens
LAYERS = (
    join(ROLLOUT, POLICY_ACT),
    join(ROLLOUT, POLICY_ACT, ATTENTION),
    join(ROLLOUT, POLICY_ACT, FFN),
    join(ROLLOUT, POLICY_ACT, LINEAR_ATTENTION),
    join(ROLLOUT, POLICY_ACT, SHORT_CONV),
    *(join(ROLLOUT, POLICY_ACT, part) for part in MOE_SCOPES),
    join(ROLLOUT, ENV_STEP, TAPE_READ),
    join(ROLLOUT, ENV_STEP, DYNAMICS),
    join(ROLLOUT, ENV_STEP, OBS),
    join(ROLLOUT, AUTO_RESET),
    join(UPDATE, GAE),
    join(UPDATE, MINIBATCH_TAKE),
    join(UPDATE, LOSS),
    join(UPDATE, LOSS, POLICY_FORWARD),
    join(UPDATE, LOSS, POLICY_FORWARD, ATTENTION),
    join(UPDATE, LOSS, POLICY_FORWARD, FFN),
    join(UPDATE, LOSS, POLICY_FORWARD, LINEAR_ATTENTION),
    join(UPDATE, LOSS, POLICY_FORWARD, SHORT_CONV),
    *(join(UPDATE, LOSS, POLICY_FORWARD, part) for part in MOE_SCOPES),
    join(UPDATE, OPTIMIZER),
    join(UPDATE, GUARD),
)
# scopes that only group others: time charged to exactly one of them (a
# scan's loop bookkeeping, an op XLA left between two layers) lies in no
# layer, and neither does time with no scope at all
GROUP_SCOPES = (ROLLOUT, UPDATE, join(ROLLOUT, ENV_STEP))

# the ``name`` of every ``pl.pallas_call`` of the step program: a Mosaic
# kernel's instruction is then ``%<name>.<n>`` whatever module it sits in
KERNEL_FILL_BRACKETS = "env_dynamics_fill_brackets"
KERNEL_MARK_REWARD = "env_dynamics_mark_reward"
KERNEL_ATTENTION_FWD = "fused_attention_fwd"
KERNEL_ATTENTION_BWD = "fused_attention_bwd"
KERNEL_GROUPED_MATMUL = "grouped_matmul"
KERNEL_GROUPED_MATMUL_DW = "grouped_matmul_dw"
KERNEL_NAMES = (KERNEL_FILL_BRACKETS, KERNEL_MARK_REWARD,
                KERNEL_ATTENTION_FWD, KERNEL_ATTENTION_BWD,
                KERNEL_GROUPED_MATMUL, KERNEL_GROUPED_MATMUL_DW)

FWD, BWD = "fwd", "bwd"


class OpScope(NamedTuple):
    path: str                  # "rollout/env_step/tape_read"
    direction: Optional[str]   # FWD under jvp(...), BWD under transpose(...)


# computation header at column 0: `%region_2.101 (arg: ...) -> ... {`
# or `ENTRY %main.2164 (...) -> ... {`
_COMPUTATION_RE = re.compile(r"(ENTRY\s+)?%?([\w.\-]+)\s*[({]")
# `  ROOT %fusion.3 = f32[8]{0} fusion(...), ..., metadata={op_name="..."}`
_INSTRUCTION_RE = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# the computation a `while` or a `fusion` runs
_CALLEE_RE = re.compile(r"\b(?:while\([^\n]*?\bbody|fusion\([^\n]*?\bcalls)=%?([\w.\-]+)")
# computations that run instruction by instruction (each one a trace
# event), as against a fusion's or a reducer's
_RUNS_RE = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}"
    r"|\bcall\([^\n]*?\bto_apply=%?([\w.\-]+)"
)
# one path component: `transpose(jvp(policy_forward))` -> wrappers, name
_COMPONENT_RE = re.compile(r"((?:\w+\()*)([^()]*)\)*$")
# the opcode after the shape, `= (f32[8]{0:T(256)}, u32[]) copy-start(%fusion.3)`,
# and the instructions its operand list names
_OPCODE_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _op_scope(op_name: str, scopes: Optional[Sequence[str]]) -> OpScope:
    """The vocabulary's names on an op path, in order, transforms
    unwrapped (a jitted helper, ``jit(name)``, is no scope).  The backward
    of a custom VJP carries the scope it is called under in front of the
    scopes it was defined under, ``loss/transpose(loss)/jvp(policy_forward)/
    .../fused_attention_bwd``, and a block rematerialised in the backward
    pass carries its whole stack again, ``loss/transpose(jvp(policy_forward))/
    .../loss/jvp(policy_forward)/.../rematted_computation/attention``: a
    name already on the path takes the path back to where it stood.

    A PART name in ``scopes`` (``PART_NAMES``) is no layer: the last one
    after the path's last layer name is put behind the rooted layer path,
    so a part extends an op's layer path and never moves it."""
    direction = BWD if "transpose(" in op_name else (
        FWD if "jvp(" in op_name else None)
    if scopes is None:
        return OpScope(op_name, direction)
    found: List[str] = []
    inside = ""
    for component in op_name.split("/"):
        match = _COMPONENT_RE.match(component)
        if match is None:
            # an op XLA merged carries every source's path, `a/b/add;jit(f)/a/c/add`:
            # the seam is no component, and the later path takes the earlier one back
            continue
        wrappers, name = match.groups()
        if name in scopes and "jit(" not in wrappers:
            if name in PART_NAMES:
                inside = name
                continue
            if name in found:
                del found[found.index(name):]
            found.append(name)
            inside = ""
    path = _rooted(join(*found))
    return OpScope(join(path, inside) if path and inside else path, direction)


def _rooted(path: str) -> str:
    """Ops traced inside a ``custom_vmap`` rule (the packing round the
    env-dynamics kernels) lose the outer part of their name stack,
    ``vmap(env_step)/dynamics/add``: a path that starts below a phase gets
    its root from the tree of layers, where only one root fits.  Constants
    hoisted out of a transformed scan body (an expert layer's ``iota``) keep
    the phase and the layer and lose what lies between, ``update/
    moe_dispatch``: such a path is the one layer of that phase it ends as."""
    if not path or path in LAYERS or path in GROUP_SCOPES:
        return path
    phase, _, tail = path.partition("/")
    if phase in PHASE_SCOPES:
        fits = [layer for layer in LAYERS
                if layer.startswith(f"{phase}/") and layer.endswith(f"/{tail}")]
        return fits[0] if len(fits) == 1 else path
    roots = set()
    for layer in LAYERS:
        at = f"/{layer}/".find(f"/{path}/")
        if at > 0:
            roots.add(layer[:at - 1])
    return join(roots.pop(), path) if len(roots) == 1 else path


def scope_map_from_hlo(
    hlo_text: str, scopes: Optional[Sequence[str]] = SCOPE_NAMES,
) -> Dict[str, OpScope]:
    """``{instruction name: OpScope}`` for the top-level instructions of
    an optimized-HLO text: those of the entry computation, of the
    ``while`` bodies and conditions and of called computations, which are
    the instructions a device trace has an event for (a fusion's insides
    run as the fusion).  ``path`` holds the members of ``scopes`` on the
    instruction's ``op_name`` path; ``scopes=None`` keeps the whole
    ``op_name``.  An instruction with no scope on its path stays out of
    the map.

    XLA's scan loops surface as ``while`` instructions that carry no
    ``op_name`` of their own yet hold self time in the trace (the loop's
    bookkeeping), and some fusions lose theirs while their insides keep
    it.  Such an instruction inherits from the computation it calls: the
    phase of the strict majority of that computation's scoped
    instructions, then as deep as all of those share a path (the rollout
    scan is ``rollout``, not one of its layers), and their direction if
    they share one.  Never raises: text that is no HLO gives ``{}``."""
    try:
        return _parse(hlo_text or "", scopes)
    except Exception:
        return {}


def part_map_from_hlo(hlo_text: str) -> Tuple[Dict[str, OpScope], Dict[str, str]]:
    """The PART map: ``scope_map_from_hlo`` with the vocabulary
    ``SCOPE_NAMES + PART_NAMES`` (a part extends its op's layer path,
    ``_op_scope``), and one rule more for the instructions XLA adds without
    metadata: an async ``*-done`` (``copy-done``, ``slice-done``) takes the
    scope its users share, as an unscoped ``while`` takes its body's
    (``_shared_scope``), its ``*-start`` the done's, and a ``copy`` its
    user's.  A done with no named user stays unnamed.  Also
    ``{instruction: opcode}`` of the top-level instructions the map leaves
    unnamed.  Never raises: text that is no HLO gives ``({}, {})``."""
    unnamed: Dict[str, str] = {}
    try:
        return _parse(hlo_text or "", SCOPE_NAMES + PART_NAMES, unnamed), unnamed
    except Exception:
        return {}, {}


def _parse(hlo_text: str, scopes, unnamed: Optional[Dict[str, str]] = None,
           ) -> Dict[str, OpScope]:
    """``unnamed`` given: the part map's rule for async pairs and copies
    applies, and the top-level instructions left unnamed go into it with
    their opcode."""
    by_computation: Dict[str, Dict[str, OpScope]] = {}
    callers: List[Tuple[str, str, str]] = []  # (name, computation, callee)
    # computation -> [(name, opcode, operands, has op_name)], in text order
    listed: Dict[str, List[Tuple[str, str, List[str], bool]]] = {}
    runs = set()
    computation = "?"
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "\t", ""):
            match = _COMPUTATION_RE.match(line)
            if match:
                computation = match.group(2)
                if match.group(1):
                    runs.add(computation)
            continue
        match = _INSTRUCTION_RE.match(line)
        if not match:
            continue
        name = match.group(1)
        at = line.rfind("metadata={")
        op_name = _OP_NAME_RE.match(line, at) if at >= 0 else None
        scope = _op_scope(op_name.group(1), scopes) if op_name else None
        if scope and scope.path:
            by_computation.setdefault(computation, {})[name] = scope
        elif scopes is not None:
            callee = _CALLEE_RE.search(line)
            if callee:
                callers.append((name, computation, callee.group(1)))
        if unnamed is not None:
            opcode = _OPCODE_RE.search(line, match.end())
            if opcode:
                listed.setdefault(computation, []).append(
                    (name, opcode.group(1), _operands(line, opcode.end()), bool(op_name)))
        for targets in _RUNS_RE.findall(line):
            for target in ",".join(targets).split(","):
                if target.strip():
                    runs.add(target.strip().lstrip("%"))
    # callees come before their callers in the text, so one pass in order
    # lets a nested scan count in its parent's body
    for name, computation, callee in callers:
        scope = _shared_scope(by_computation.get(callee, {}).values())
        if scope.path:
            by_computation.setdefault(computation, {})[name] = scope
    out: Dict[str, OpScope] = {}
    for computation in runs:
        named = by_computation.get(computation, {})
        if unnamed is not None:
            _name_async_and_copies(listed.get(computation, []), named)
            unnamed.update((name, opcode) for name, opcode, *_ in listed.get(computation, [])
                           if name not in named)
        out.update(named)
    return out


def _operands(line: str, at: int) -> List[str]:
    """The instruction names in the operand list that opens at ``at``: the
    compiled text prints an operand as its name alone, so the list ends at
    the first ``)`` (a constant's literal, megabytes on one line, is read
    at the speed of ``str.find``)."""
    return _OPERAND_RE.findall(line, at, line.find(")", at))


def _name_async_and_copies(listed, named: Dict[str, OpScope]) -> None:
    """The part map's rule, in one computation: walked from its last
    instruction to its first, so that a done's users (a copy among them)
    and a start's done are named before it is."""
    users: Dict[str, List[str]] = {}
    for name, _opcode, operands, _has_op_name in listed:
        for operand in operands:
            users.setdefault(operand, []).append(name)
    for name, opcode, _operands, has_op_name in reversed(listed):
        if has_op_name or name in named or not (
                opcode == "copy" or opcode.endswith(("-start", "-done"))):
            continue
        scope = _shared_scope(named[user] for user in users.get(name, ()) if user in named)
        if scope.path:
            named[name] = scope


def _shared_scope(called) -> OpScope:
    called = list(called)
    paths = [scope.path.split("/") for scope in called]
    phases = [path[0] for path in paths]
    phase = max(set(phases), key=phases.count) if phases else ""
    if phases.count(phase) * 2 <= len(phases):
        return OpScope("", None)
    paths = [path for path in paths if path[0] == phase]
    shared = 0
    while all(len(p) > shared and p[shared] == paths[0][shared] for p in paths):
        shared += 1
    directions = {scope.direction for scope in called}
    return OpScope(join(*paths[0][:shared]),
                   directions.pop() if len(directions) == 1 else None)


# ---------------------------------------------------------------------------
# the newest step program handed out (bench_util.compile_train_step)
# ---------------------------------------------------------------------------
_executable: Any = None
_scope_map: Optional[Dict[str, OpScope]] = None
_part_map: Optional[Dict[str, OpScope]] = None
_unnamed: Optional[Dict[str, str]] = None
# what building the newest step's maps cost the host (a traced run's cost)
build_cost: Dict[str, float] = {}


def register_step(executable: Any) -> None:
    """Remember the newest step executable, and only it: registering does
    no work (the text of the flagship step is megabytes), and an older
    program is let go the moment a newer one is handed out."""
    global _executable, _scope_map, _part_map, _unnamed
    _executable, _scope_map, _part_map, _unnamed = executable, None, None, None


def _build_maps() -> None:
    """Both maps of the newest step from ONE text, when either is first
    asked for; the executable is let go then and the maps kept."""
    global _executable, _scope_map, _part_map, _unnamed
    if _executable is None:
        return
    text, _executable = _executable.as_text(), None
    started = time.perf_counter()
    _scope_map = scope_map_from_hlo(text)
    built = time.perf_counter()
    _part_map, _unnamed = part_map_from_hlo(text)
    build_cost.update(scope_map_s=built - started,
                      part_map_s=time.perf_counter() - built, text_bytes=len(text))


def last_step_scope_map() -> Optional[Dict[str, OpScope]]:
    """The scope map of the newest step program handed out, or ``None``
    when there was none."""
    _build_maps()
    return _scope_map


def last_step_part_map() -> Optional[Dict[str, OpScope]]:
    """The part map (``part_map_from_hlo``) of the newest step program
    handed out, or ``None`` when there was none."""
    _build_maps()
    return _part_map


def last_step_unnamed() -> Optional[Dict[str, str]]:
    """``{instruction: opcode}`` of the newest step's top-level instructions
    that its part map leaves unnamed, or ``None`` when there was no step."""
    _build_maps()
    return _unnamed
