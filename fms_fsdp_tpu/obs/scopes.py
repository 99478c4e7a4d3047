"""``jax.named_scope`` names, and the way back from a device event to one.

A scan-of-blocks model shows in a profiler trace as one undifferentiated
``while`` region of fusions whose names (``fusion.13.remat4``) change
with every compile. ``scoped("name")`` (or ``jax.named_scope`` directly)
puts ``name`` into the ``op_name`` metadata of every operation traced
under it: zero runtime cost (metadata only, the compiled arithmetic and
its instruction names do not change), safe inside jit/scan/remat.

On this jax the scope does **not** reach the trace by itself: a device
event of the ``.xplane.pb`` is named by its instruction's HLO text and
carries no ``op_name``. It is in the compiled executable's HLO text,
though, as ``metadata={op_name="jit(_step)/while/body/.../moe_gather/
gather"}`` on every instruction, under the same instruction names
(``%fusion.300``) that the trace's events carry. ``scope_table`` reads
that text into ``{instruction name: scope}``; joining a trace's device
events with it by name gives each event its scope
(benchmark/program_trace.py does so for the decode program;
docs/observability.md "Profiler trace attribution"). The trace's
``/host:metadata`` plane carries the HLO proto of each executed module
too, but it is the proto of the executable that was *loaded*: the
persistent compile cache leaves metadata out of its key and may load one
that an older tree compiled, with that tree's names. So compile afresh,
with the cache off, for a table of this tree's scopes.
"""

import functools
import re
from typing import Dict, Iterable

import jax

# the scopes of one serving decode step, in program order
# (models/generation.py, ops/paged_attention.py, models/mixtral.py,
# serve/decode.py, the adapters' jitted ``_step``). ``layers`` is around
# the layer scan: what lies under it and under no inner scope is the
# scan's own slicing of the stacked layer arrays
DECODE_SCOPES = (
    "params_cast",
    "rope",
    "embed",
    "layers",
    "qkv",
    "kv_write",
    "kv_gather",
    "attn",
    "attn_out",
    "ffn",
    "moe_router",
    "moe_gather",
    "moe_experts",
    "moe_combine",
    "moe_dense",
    "lm_head",
    "sample",
)

# the scopes of a hybrid engine's two programs (models/mamba.py,
# serve/families/mamba.py: ``jit__step`` and ``jit__prefill_<tokens>``),
# in program order: the Mamba-1 mixer's five, the attention layers', and
# what every layer shares. Nothing of a Mamba-1 step lies under none
HYBRID_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "ssm_in_proj",
    "ssm_conv",
    "ssm_params",
    "ssm_scan",
    "ssm_gate_out",
    "qkv",
    "kv_write",
    "kv_gather",
    "attn",
    "attn_out",
    "mlp",
    "lm_head",
    "sample",
)
SSM_SCOPES = tuple(s for s in HYBRID_SCOPES if s.startswith("ssm_"))

# the scopes of a sarvam engine's two programs (models/sarvam.py,
# serve/families/sarvam.py: ``jit__step`` and ``jit__prefill_<tokens>``),
# in program order. ``mla_kv_down`` holds ``W_kva``, the latent's norm and
# the rotary tables; ``mla_absorb`` the decode step's queries through
# ``W_kvb^K`` and outputs through ``W_kvb^V``; ``mla_expand`` the
# prefill's ``W_kvb`` over a block of the latent; ``moe_group`` the
# prefill's sort of its (token, choice) pairs by held expert. ``layers``
# is around the scan over the MoE layers: its own slicing of the stack
SARVAM_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "layers",
    "mla_q",
    "mla_kv_down",
    "latent_write",
    "latent_gather",
    "mla_absorb",
    "mla_expand",
    "attn",
    "attn_out",
    "mlp",
    "moe_router",
    "moe_shared",
    "moe_group",
    "moe_experts",
    "moe_combine",
    "lm_head",
    "sample",
)

# the scopes of a kexaone engine's two programs (models/kexaone.py,
# serve/families/kexaone.py: ``jit__step`` and ``jit__prefill_<tokens>``),
# in program order; the two kinds of attention layer are told apart in
# every phase. Window layers: ``win_write`` (the ring's write in a decode
# step; in a prefill the carry of a chunk's last ``sliding_window``
# positions and the ring's order at the end) and ``attn_window`` (the
# ring's attention; the windowed flash kernel over a chunk's band and the
# carried positions' part). Full layers: ``kv_write`` (the page write; the
# prefill's buffer write), ``kv_read`` (the reference's gather of pages;
# nothing under the kernel, which reads them itself) and ``attn_full``
# (the ragged paged kernel or the gathered attention; the prefill's walk
# over the chunk's own and earlier blocks). ``qk_norm`` is the RMSNorm by
# head of q and k, ``rope`` the window layers' rotary embedding; the
# ``moe_*`` scopes are models/moe_held.py's, as in ``SARVAM_SCOPES``.
# ``layers`` is around the (unrolled) stack
KEXAONE_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "layers",
    "qkv",
    "qk_norm",
    "rope",
    "win_write",
    "attn_window",
    "kv_write",
    "kv_read",
    "attn_full",
    "attn_out",
    "mlp",
    "moe_router",
    "moe_shared",
    "moe_group",
    "moe_experts",
    "moe_combine",
    "lm_head",
    "sample",
)

# the scopes of a minicpm_sala engine's two programs
# (models/minicpm_sala.py, serve/families/minicpm_sala.py: ``jit__step``
# and ``jit__prefill_<tokens>``), in program order; the two kinds of
# mixer are told apart in every phase. Sparse layers: ``kv_write`` (the
# page write; the prefill's buffer write), ``sparse_compress`` (a
# prefill chunk's compressed keys, the windows that straddle its start
# among them), ``index_write`` (the decode step's compressed key, when
# its position completes a window), ``sparse_select`` (the scores
# against the compressed keys, their pooling to blocks, the top-k),
# ``sparse_attn`` (the chosen pages read and attended; in a prefill the
# walk under each query's mask of chosen blocks) and ``attn`` (a prefill
# chunk whose every position is still dense). Lightning layers:
# ``lin_scan`` (a prompt's chunked form), ``lin_step`` (the one-position
# update of the state) and ``lin_gate`` (the output norm and gate).
# ``qk_norm`` is the RMSNorm by head of q and k, ``rope`` the lightning
# layers' rotary embedding. ``layers`` is around the (unrolled) stack
SALA_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "layers",
    "qkv",
    "qk_norm",
    "rope",
    "kv_write",
    "sparse_compress",
    "index_write",
    "sparse_select",
    "sparse_attn",
    "attn",
    "lin_scan",
    "lin_step",
    "lin_gate",
    "attn_out",
    "mlp",
    "lm_head",
    "sample",
)

# the scopes of an lfm2 engine's two programs (models/lfm2.py,
# serve/families/lfm2.py: ``jit__step`` and ``jit__prefill_<tokens>``), in
# program order. Convolution layers: ``conv_in`` (``W_in`` and the gate
# ``B * x``), ``short_conv`` (the taps over the window and the position,
# the window's shift in a decode step; in a prefill the carry of a
# chunk's last positions) and ``conv_out`` (the gate ``C *`` and
# ``W_out``). Attention layers: the names ``KEXAONE_SCOPES`` gives its
# full layers (``kv_write``, ``kv_read``, ``attn_full``), ``qk_norm`` and
# ``rope`` as there. ``dense_mlp`` is the leading layers' SwiGLU, the
# ``moe_*`` scopes are models/moe_held.py's (``moe_router`` holds the
# count of the experts the live streams chose), ``head`` the final norm
# and the tied head. ``layers`` is around the (unrolled) stack
LFM2_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "layers",
    "conv_in",
    "short_conv",
    "conv_out",
    "qkv",
    "qk_norm",
    "rope",
    "kv_write",
    "kv_read",
    "attn_full",
    "attn_out",
    "dense_mlp",
    "moe_router",
    "moe_group",
    "moe_experts",
    "moe_combine",
    "head",
    "sample",
)

# the scopes of a phi4flash engine's two programs (models/phi4flash.py,
# serve/families/phi4flash.py: ``jit__step`` and ``jit__prefill_<tokens>``),
# in program order. Mamba layers: the five ``ssm_*`` scopes of
# ``HYBRID_SCOPES`` (the slab's masked update of a decode step lies under
# ``ssm_scan``). Window layers: ``win_write`` and ``attn_window`` as in
# ``KEXAONE_SCOPES``. The full layer: ``kv_write`` (the page write; the
# prefill's buffer write, every position's) and ``attn_full``; the cross
# layers: ``attn_cross``, their reads of the full layer's pages.
# ``diff_combine`` (the lambda, the difference of the two softmaxes'
# outputs, the norm by head) lies inside whichever of ``attn_window``,
# ``attn_full`` and ``attn_cross`` it follows: a table over these names
# gives it alone, one over ``PHI4FLASH_SCOPES_COARSE`` leaves it with its
# attention. ``gmu`` is a gated memory unit, ``qkv`` the projections with
# their biases and the queries' rows. ``layers`` is around the (unrolled)
# stack
PHI4FLASH_SCOPES = (
    "params_cast",
    "embed",
    "norm",
    "layers",
    "ssm_in_proj",
    "ssm_conv",
    "ssm_params",
    "ssm_scan",
    "ssm_gate_out",
    "qkv",
    "win_write",
    "attn_window",
    "kv_write",
    "attn_full",
    "attn_cross",
    "diff_combine",
    "attn_out",
    "gmu",
    "mlp",
    "lm_head",
    "sample",
)
PHI4FLASH_SCOPES_COARSE = tuple(
    s for s in PHI4FLASH_SCOPES if s != "diff_combine"
)

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
# the computations an instruction runs: a loop's body and condition, a
# fusion's or a call's computation, a conditional's branches
_CALLED = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}"
)


def scoped(name: str):
    """Decorator: run the wrapped trace function under
    ``jax.named_scope(name)``."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return deco


def scope_table(
    hlo_text: str, names: Iterable[str] = DECODE_SCOPES
) -> Dict[str, str]:
    """``{instruction name: scope}`` of an optimized HLO module's text
    (``jitted.lower(...).compile().as_text()``), for every instruction.

    An instruction's scope is the innermost component of its ``op_name``
    path that is one of ``names``. An instruction with no ``op_name`` at
    all (the compiler made it while expanding another: the loop of slices
    that a gather becomes, the copies that put the slices together) takes
    the scope of what it consumes: the first of its operands that has one
    of its own or by this same rule. Failing that, and where an
    ``op_name`` names no scope, it takes the scope of the instruction
    that runs its computation: the ``while`` whose body it is in, the
    fusion, the call. ``""`` where nothing gives one. A fusion carries the
    ``op_name`` of its root, so a fusion that the compiler built across
    two scopes counts wholly under its root's. Instruction names are
    unique in a module; those inside fused computations are listed too
    and do no harm, the trace has no events for them.
    """
    names = frozenset(names)
    # local: the scope an instruction has of itself or of what it consumes
    # (a computation's text lists operands before their users)
    local, home, caller = {}, {}, {}  # caller: by computation
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        scope = next(
            (p for p in reversed(op.group(1).split("/")) if p in names), ""
        ) if op else ""
        if op is None:
            scope = next(
                (local[o] for o in _NAME.findall(line, m.end())
                 if local.get(o) and home[o] == computation), "")
        local[name], home[name] = scope, computation
        for one, several in _CALLED.findall(line):
            for called in [one] if one else several.split(","):
                caller[called.strip().lstrip("%")] = name

    def scope_of(name):
        seen = set()
        while name is not None and name not in seen:
            if local[name]:
                return local[name]
            seen.add(name)
            name = caller.get(home[name])
        return ""

    return {name: scope_of(name) for name in local}
