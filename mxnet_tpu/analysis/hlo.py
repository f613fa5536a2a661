"""mxlint level 2 — named checks on the LOWERED program artifact.

``tests/test_hlo_perf.py`` proved the pattern: everything under ``jit``
is one inspectable StableHLO/HLO module, so the properties that
*determine* TPU throughput (layout, FLOPs, remat structure, collective
overlap, host transfers) can be asserted on the artifact with zero
devices.  This module factors those ad-hoc assertions into reusable
named checks callable from tests AND from ``tools/mxlint.py --hlo`` on
an exported artifact — the mixed imperative/symbolic design's payoff:
the symbolic program is itself a lintable object.

Checks return :class:`HloCheckResult` (never raise on a finding):
``ok`` plus human-readable ``details`` naming each violation, so a test
asserts ``res.ok, res.details`` and the CLI prints the same text.

Everything here is pure text analysis (``re`` only — no jax import),
so it runs wherever the lint runs.
"""
from __future__ import annotations

import re

__all__ = [
    "HloCheckResult", "TEXT_CHECKS", "run_text_checks",
    "conv_signatures", "conv_dim_numbers", "conv_flops", "count_convs",
    "rank_ge3_transposes", "host_transfer_sites", "all_gather_results",
    "collective_counts",
    "check_transpose_free", "check_convs_channel_minor",
    "check_no_host_transfers", "check_no_full_param_all_gather",
    "check_collective_permute_overlap", "check_collective_overlap",
    "check_overlap_window", "check_collective_present",
    "check_remat_recompute",
]


class HloCheckResult:
    def __init__(self, name, ok, details=()):
        self.name = name
        self.ok = bool(ok)
        self.details = list(details)

    def __bool__(self):
        return self.ok

    def __repr__(self):
        return "HloCheckResult(%s, %s%s)" % (
            self.name, "ok" if self.ok else "FAIL",
            "" if self.ok else ": " + "; ".join(self.details[:5]))


# ----------------------------------------------------------------------
# low-level extractors (the regexes test_hlo_perf.py pinned)
# ----------------------------------------------------------------------
_CONV_SIG = re.compile(
    r"stablehlo\.convolution.*?:\s*\(tensor<([^>]+)>,\s*tensor<([^>]+)>\)"
    r"\s*->\s*tensor<([^>]+)>")
_CONV_DNUMS = re.compile(
    r"stablehlo\.convolution[^:]*dim_numbers = "
    r"\[([^\]]*)\]x\[([^\]]*)\]->\[([^\]]*)\]")
_TRANSPOSE = re.compile(r"stablehlo\.transpose[^\n]*-> tensor<([^>]+)>")
# StableHLO spells the result after '->'; compiled HLO puts the result
# shape BEFORE the op name ('%ag = f32[128,64]{1,0} all-gather(...)')
_ALL_GATHER_STABLE = re.compile(
    r"stablehlo\.all_gather[^\n]*->\s*tensor<([^>]+)>")
_ALL_GATHER_COMPILED = re.compile(
    r"=\s*\w+\[([0-9,]*)\][^\n ]*\s+all-gather(?:-start)?\(")
# host<->device traffic markers: stablehlo + compiled-HLO spellings
_HOST_XFER = re.compile(
    r"stablehlo\.(?:infeed|outfeed|send|recv)\b"
    r"|\b(?:infeed|outfeed|send(?:-start)?|recv(?:-start)?)\("
    r"|MoveToHost|MoveFromHost|host_compute|HostCompute")


def _shape_of(tensor_sig):
    """``'8x224x224x3xbf16'`` -> (8, 224, 224, 3)."""
    return tuple(int(d) for d in tensor_sig.split("x")[:-1])


def conv_signatures(txt):
    """Per-convolution ((lhs), (w), (out)) shape tuples of a lowered
    module."""
    return [tuple(_shape_of(s) for s in m.groups())
            for m in _CONV_SIG.finditer(txt)]


def conv_dim_numbers(txt):
    """Per-convolution (lhs, rhs, out) dim-number strings."""
    return _CONV_DNUMS.findall(txt)


def count_convs(txt):
    return len(re.findall(r"stablehlo\.convolution", txt))


def conv_flops(txt):
    """Analytic hardware FLOPs of every convolution in a lowered module
    from its tensor shapes: 2 * N*Ho*Wo*O * kh*kw*I per conv (channel-
    minor dim numbers asserted separately by
    :func:`check_convs_channel_minor`)."""
    total = 0
    for _, w, out in conv_signatures(txt):
        n, ho, wo, o = out
        o2, kh, kw, i = w
        total += 2 * n * ho * wo * o * kh * kw * i
    return total


def rank_ge3_transposes(txt):
    """Result shapes of every rank>=3 transpose — on TPU each is a real
    re-layout kernel the NHWC path exists to avoid."""
    return [t for t in _TRANSPOSE.findall(txt) if t.count("x") >= 3]


def host_transfer_sites(txt):
    """(line-number, line) of every host-transfer marker."""
    out = []
    for i, line in enumerate(txt.splitlines(), 1):
        if _HOST_XFER.search(line):
            out.append((i, line.strip()[:120]))
    return out


#: collective kinds -> regex matching BOTH the StableHLO spelling and
#: the compiled-HLO spelling (sync or async-start form)
_COLLECTIVE_RES = {
    "collective_permute": re.compile(
        r"stablehlo\.collective_permute\b"
        r"|collective-permute(?:-start)?\("),
    "all_reduce": re.compile(
        r"stablehlo\.all_reduce\b|all-reduce(?:-start)?\("),
    "all_gather": re.compile(
        r"stablehlo\.all_gather\b|all-gather(?:-start)?\("),
    "reduce_scatter": re.compile(
        r"stablehlo\.reduce_scatter\b|reduce-scatter\("),
    "all_to_all": re.compile(
        r"stablehlo\.all_to_all\b|all-to-all\("),
}


def collective_counts(txt):
    """``{kind: occurrence count}`` over every known collective kind, in
    either StableHLO or compiled-HLO spelling."""
    return {k: len(rx.findall(txt)) for k, rx in _COLLECTIVE_RES.items()}


def all_gather_results(txt):
    """Result shapes (tuples) of every all-gather in the module, in
    either StableHLO or compiled-HLO spelling."""
    shapes = [_shape_of(m.group(1))
              for m in _ALL_GATHER_STABLE.finditer(txt)]
    for m in _ALL_GATHER_COMPILED.finditer(txt):
        shapes.append(tuple(int(d) for d in m.group(1).split(",") if d))
    return shapes


# ----------------------------------------------------------------------
# named program checks
# ----------------------------------------------------------------------
def check_transpose_free(txt):
    """No rank>=3 transposes: activations never leave the TPU-native
    feature-last layout in either direction of the program."""
    bad = rank_ge3_transposes(txt)
    return HloCheckResult(
        "transpose_free", not bad,
        ["rank>=3 transpose -> tensor<%s>" % t for t in bad[:10]])


def check_convs_channel_minor(txt):
    """Every convolution's operand/output dim numbers keep spatial dims
    in the middle with batch/feature on the outside (fwd ``[b,0,1,f]``,
    wgrad ``[f,0,1,b]``) — channel-minor operands, no NCHW-style
    spatial-minor form anywhere, so TPU layout assignment is the
    identity."""
    details = []
    dimnums = conv_dim_numbers(txt)
    if len(dimnums) != count_convs(txt):
        details.append("dim_numbers parsed for %d of %d convolutions"
                       % (len(dimnums), count_convs(txt)))
    for lhs, rhs, out in dimnums:
        for part in (lhs, out):
            dims = part.replace(" ", "").split(",")
            if dims[1:3] != ["0", "1"] or sorted(dims[::3]) != ["b", "f"]:
                details.append("spatial-minor conv operand [%s]" % part)
    return HloCheckResult("convs_channel_minor", not details, details)


def check_no_host_transfers(txt):
    """No infeed/outfeed/send/recv/host-compute in the program: a step
    that silently bounces through the host caps throughput at PCIe-or-
    worse regardless of what the MXU does."""
    sites = host_transfer_sites(txt)
    return HloCheckResult(
        "no_host_transfers", not sites,
        ["line %d: %s" % s for s in sites[:10]])


def check_no_full_param_all_gather(txt, param_shapes=()):
    """Under ZeRO-1 the only gathered state is the per-shard slice; an
    all-gather whose RESULT is a full parameter shape means the sharding
    degenerated to replicate-everything (the memory win is gone).
    ``param_shapes``: full (unsharded) parameter shapes to screen
    against."""
    params = {tuple(s) for s in param_shapes}
    if not params:
        # without shapes to screen against the check proves nothing —
        # say so instead of printing a vacuous 'ok'
        return HloCheckResult(
            "no_full_param_all_gather", True,
            ["note: no param_shapes supplied — screen skipped "
             "(pass --hlo-param-shapes / param_shapes=)"])
    bad = [s for s in all_gather_results(txt) if s in params]
    return HloCheckResult(
        "no_full_param_all_gather", not bad,
        ["all-gather materializes full parameter %r" % (s,)
         for s in bad[:10]])


def check_collective_permute_overlap(txt, require_present=False):
    """Ring/pipeline neighbor exchanges overlap compute only when the
    compiled HLO carries them in async form — every collective-permute
    split into a ``-start``/``-done`` pair (XLA can then schedule the
    flash kernel between the two).  A synchronous ``collective-permute(``
    is a bubble the ring-overlap work must eliminate."""
    # paren-anchored: count op definitions/calls, not `%...-start`
    # operand references
    starts = len(re.findall(r"collective-permute-start\(", txt))
    dones = len(re.findall(r"collective-permute-done\(", txt))
    sync = len(re.findall(r"collective-permute\(", txt))
    details = []
    if sync:
        details.append("%d synchronous collective-permute ops (no "
                       "start/done overlap window)" % sync)
    if starts != dones:
        details.append("unbalanced async pairs: %d starts, %d dones"
                       % (starts, dones))
    if require_present and starts == 0:
        details.append("no collective-permute-start at all — the ring "
                       "exchange is missing or fused away")
    return HloCheckResult("collective_permute_overlap", not details,
                          details)


#: collective kind -> compiled-HLO spelling stem (async forms append
#: ``-start``/``-done``; the sync form is ``<stem>(``)
_COLLECTIVE_STEMS = {
    "collective_permute": "collective-permute",
    "all_reduce": "all-reduce",
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "all_to_all": "all-to-all",
}


def _strip_async_fusion_bodies(txt):
    """Drop the bodies of ``%async_collective_fusion...`` computations:
    the collective op inside them is spelled synchronously but IS the
    async implementation (the TPU backend wraps async collectives into
    fusion computations called from ``async-collective-start``)."""
    out, skipping = [], False
    for line in txt.splitlines():
        if line.startswith("%async_collective_fusion"):
            skipping = True
        if not skipping:
            out.append(line)
        if skipping and line.startswith("}"):
            skipping = False
    return "\n".join(out)


def check_collective_overlap(txt, kinds=("collective_permute",),
                             require_present=False, allow_sync=False):
    """Generalization of :func:`check_collective_permute_overlap` to any
    collective kind: each named collective must appear in the compiled
    artifact ONLY in async form — an explicit ``<kind>-start``/
    ``<kind>-done`` pair, or the TPU backend's
    ``async-collective-start`` fusion wrapper (attributed via its
    ``async_collective_name="<kind>-start..."`` frontend attribute).
    XLA can then schedule compute inside the window — a ZeRO-1 gradient
    reduce overlapping the backward tail, the updated-param all-gather
    overlapping remaining compute.  A synchronous ``<kind>(`` op
    outside any async wrapper is a serial bubble.  ``kinds`` use
    :data:`collective_counts` vocabulary; unnamed kinds are ignored (a
    program may legitimately carry sync collectives on paths the check
    does not govern).  ``allow_sync=True`` relaxes the no-sync half for
    artifacts where the scheduler legitimately asyncifies only the
    profitable subset (e.g. a ZeRO-1 step whose small bias gathers stay
    sync while every weight gather overlaps) — presence and pairing are
    still enforced."""
    stripped = _strip_async_fusion_bodies(txt)
    details = []
    wrapper_starts = len(re.findall(r"%async-collective-start[.\d]* = ",
                                    txt))
    wrapper_dones = len(re.findall(r"%async-collective-done[.\d]* = ",
                                   txt))
    if wrapper_starts != wrapper_dones:
        details.append("unbalanced async-collective wrappers: %d starts,"
                       " %d dones" % (wrapper_starts, wrapper_dones))
    for kind in kinds:
        stem = _COLLECTIVE_STEMS.get(kind)
        if stem is None:
            details.append("unknown collective kind %r (known: %s)"
                           % (kind, ", ".join(sorted(_COLLECTIVE_STEMS))))
            continue
        starts = len(re.findall(re.escape(stem) + r"-start\(", stripped))
        dones = len(re.findall(re.escape(stem) + r"-done\(", stripped))
        wrapped = len(re.findall(
            r'async_collective_name="' + re.escape(stem) + r"-start",
            txt))
        sync = len(re.findall(re.escape(stem) + r"\(", stripped))
        if sync and not allow_sync:
            details.append("%d synchronous %s ops (no start/done "
                           "overlap window)" % (sync, stem))
        if starts != dones:
            details.append("unbalanced async %s pairs: %d starts, "
                           "%d dones" % (stem, starts, dones))
        if require_present and starts + wrapped == 0:
            details.append("no async %s at all — the %s is missing or "
                           "fused away" % (stem, kind))
    return HloCheckResult("collective_overlap", not details, details)


def check_overlap_window(txt, min_windows=1):
    """The compiled module is SCHEDULED (``is_scheduled=true``):
    instruction order in the text is execution order.  For every async
    collective start (explicit ``*-start`` op or
    ``async-collective-start`` wrapper), count the real compute ops
    (fusions, convolutions, dots, custom-calls) scheduled between it and
    its matching done — the overlap window.  At least ``min_windows``
    pairs must have a non-empty window: an artifact where every done
    immediately follows its start pays the full hop latency serially,
    exactly the bubble the double-buffer/overlap work exists to
    remove."""
    compute_re = re.compile(
        r"= \S+ (?:fusion|convolution[\w-]*|dot|custom-call)\(")
    lhs_re = re.compile(r"^\s*(?:ROOT\s+)?%(\S+?) = ")
    # a start/done is recognized by EITHER spelling: the op on the rhs
    # (`... = f32[...] collective-permute-start(...)`) or the bound
    # name on the lhs (the TPU wrapper `%async-collective-start = (...)
    # fusion(...)`); memory ops (copy/slice) are not collectives
    start_mark = re.compile(r"\b[a-z][\w-]*-start[.\d]*[ (=]")
    done_mark = re.compile(r"\b[a-z][\w-]*-done[.\d]*[ (=]")
    mem_mark = re.compile(r"\b(?:copy|slice)-(?:start|done)")
    windows = []
    # explicit `<op>-start` ops are matched to the done that names them
    # as an operand; `async-collective-start` fusion wrappers return a
    # tuple consumed via get-tuple-elements, so wrappers pair with the
    # next wrapper-done in schedule order instead
    pending = []  # [[name, compute_ops_since_start]]
    for line in txt.splitlines():
        m = lhs_re.search(line)
        if m is None:
            continue
        name = m.group(1)
        if start_mark.search(line) and not done_mark.search(line) \
                and not mem_mark.search(line):
            pending.append([name, 0])
            continue
        if done_mark.search(line) and not mem_mark.search(line) \
                and pending:
            matched = None
            for entry in pending:
                if "%" + entry[0] + ")" in line or \
                        "%" + entry[0] + "," in line:
                    matched = entry
                    break
            if matched is None and "async-collective-done" in line:
                for entry in pending:
                    if "async-collective-start" in entry[0]:
                        matched = entry
                        break
            if matched is not None:
                pending.remove(matched)
                windows.append((matched[0], matched[1]))
                continue
        if compute_re.search(line):
            for entry in pending:
                entry[1] += 1
    details = []
    if not windows:
        details.append("no async collective start/done pairs found")
    elif sum(1 for _, w in windows if w > 0) < min_windows:
        details.append(
            "every async collective done is scheduled immediately after "
            "its start (no compute in any window): %s"
            % ", ".join("%s+%d" % p for p in windows[:8]))
    return HloCheckResult("overlap_window", not details, details)


def check_collective_present(txt, kinds=("collective_permute",)):
    """The named collectives actually appear in the lowered program —
    the existence half of a parallel-path assertion: a pipeline/ring
    schedule whose neighbor exchange got traced away (or never
    partitioned) silently degenerates to single-device compute, and
    every *overlap* check on it passes vacuously.  ``kinds`` come from
    :data:`collective_counts`' vocabulary."""
    counts = collective_counts(txt)
    details = []
    for k in kinds:
        if k not in counts:
            details.append("unknown collective kind %r (known: %s)"
                           % (k, ", ".join(sorted(counts))))
        elif counts[k] == 0:
            details.append("no %s in the program — the exchange is "
                           "missing, fused away, or never partitioned"
                           % k)
    return HloCheckResult("collective_present", not details, details)


def check_remat_recompute(base_txt, remat_txt, min_extra_convs=1):
    """``jax.checkpoint`` changed the PROGRAM: the remat module carries
    the forward convolutions a second time (recompute-in-backward)
    behind an ``optimization_barrier``.  Chip-independent form of the
    bandwidth<->compute trade (the backend may still CSE it — that is a
    scheduler property, not a program one)."""
    base, remat = count_convs(base_txt), count_convs(remat_txt)
    details = []
    if remat < base + min_extra_convs:
        details.append("remat program has %d convs vs %d base (expected "
                       ">= +%d recompute)" % (remat, base,
                                              min_extra_convs))
    if "optimization_barrier" not in remat_txt:
        details.append("remat program lost its optimization_barrier")
    return HloCheckResult("remat_recompute", not details, details)


#: Single-artifact checks ``mxlint --hlo`` runs on an exported module.
TEXT_CHECKS = {
    "transpose_free": check_transpose_free,
    "convs_channel_minor": check_convs_channel_minor,
    "no_host_transfers": check_no_host_transfers,
    "no_full_param_all_gather": check_no_full_param_all_gather,
    "collective_permute_overlap": check_collective_permute_overlap,
    "collective_overlap": check_collective_overlap,
    "overlap_window": check_overlap_window,
    "collective_present": check_collective_present,
}


def run_text_checks(txt, names=None, **kwargs):
    """Run the named single-artifact checks (default: all) over one
    lowered/compiled module text; kwargs reach same-named check
    parameters (e.g. ``param_shapes=...``)."""
    import inspect
    out = []
    for name in names or sorted(TEXT_CHECKS):
        fn = TEXT_CHECKS[name]
        accepted = set(inspect.signature(fn).parameters) - {"txt"}
        out.append(fn(txt, **{k: v for k, v in kwargs.items()
                              if k in accepted}))
    return out
