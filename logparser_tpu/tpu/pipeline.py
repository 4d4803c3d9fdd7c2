"""Fused device pipeline: split + typed post-stages -> ONE packed [K, B] int32.

Plain-XLA execution everywhere (TPU and the CPU test meshes).  This is the
rebuild's answer to the reference's per-line `Matcher.find()` hot loop
(TokenFormatDissector.java:243-275): a compiled split program executed as a
vector automaton, not a backtracking regex.  The workload — elementwise
compares + masked reductions — is exactly the shape XLA's fusion engine
schedules near-optimally on the VPU; a hand-written Pallas kernel of the
same pipeline measured ~4.5x SLOWER on v5e (one HBM pass either way, and
the kernel's lane rolls cost more than XLA's fused selects) and Mosaic
cannot lower the chained stages at all, so the kernel was removed (see
COMPONENTS.md, "Pallas kernel" ADR; round-2 measurements in git history).

The output is a single packed ``[K, B]`` int32 array (one row per output
component, described by :class:`PackedLayout`) so the host needs exactly ONE
device->host fetch per batch: one transfer round-trip, however many fields.

Shift discipline: every data movement is a left-shift of the line axis with
a zero-filled tail (``shift_zero``); callers mask every position past the
span/line end.
"""
from __future__ import annotations

from dataclasses import dataclass, field as dataclass_field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import postproc, timeparse
from .program import CS_ANY, DeviceProgram


@dataclass(frozen=True)
class FieldPlan:
    """How one requested field is produced on device ('host' = oracle-only).

    A plan is a token capture plus a chain of span-transform ``steps``
    (the device analogues of sub-dissectors: first-line split, URI split,
    ...) ending in a terminal decode ``kind``:

    - ``span``      the final sub-span itself (string field)
    - ``long``      digit span -> int64 (null_mode handles the CLF '-' and
                    zero<->null converter semantics)
    - ``secmillis`` "sec.millis" decimal span -> epoch millis
    - ``ts``        fixed-layout timestamp -> component bundle; ``comp``
                    names the requested output (epoch/year/.../monthname)
                    and ``meta`` carries the DeviceTimeLayout
    - ``host``      oracle-only
    """

    field_id: str                 # cleaned "TYPE:path"
    kind: str                     # span | long | secmillis | ts | host
    token_index: int = -1
    steps: Tuple[Tuple[str, str], ...] = ()   # e.g. (("fl", "uri"),)
    comp: str = ""                # ts output name / CSR wildcard key
    meta: object = None           # ts: DeviceTimeLayout; qscsr: mode
    null_mode: str = ""           # "" | dash_null | dash_zero | zero_null
    scale: int = 1                # value multiplier (ms -> us converters)
    # qscsr set-cookie only: the per-cookie attribute requested THROUGH the
    # wildcard (value/expires/path/domain/comment); comp is the cookie name.
    # Materialized host-side per matched row (cookies.parse_attrs).
    attr: str = ""


# ---------------------------------------------------------------------------
# Shifts: the only data-movement primitive in the pipeline.
# ---------------------------------------------------------------------------


from .postproc import shift_zero  # the shared zero-fill shift primitive


# ---------------------------------------------------------------------------
# Split program (shared by runtime.run_program and the packed pipeline).
# ---------------------------------------------------------------------------


def _table_intervals(table: np.ndarray) -> List[Tuple[int, int]]:
    """Decompose a 256-entry bool charset table into [lo, hi] byte intervals,
    so membership compiles to a few vector compares instead of a gather."""
    intervals: List[Tuple[int, int]] = []
    lo = None
    for b in range(257):
        inside = b < 256 and bool(table[b])
        if inside and lo is None:
            lo = b
        elif not inside and lo is not None:
            intervals.append((lo, b - 1))
            lo = None
    return intervals


def _charset_mask(b32: jnp.ndarray, table: np.ndarray) -> jnp.ndarray:
    """[B, L] bool: byte admitted by the charset, via interval compares."""
    intervals = _table_intervals(table)
    if not intervals:
        return jnp.zeros(b32.shape, dtype=bool)
    if len(intervals) == 1 and intervals[0] == (0, 255):
        return jnp.ones(b32.shape, dtype=bool)
    ok = None
    for lo, hi in intervals:
        part = (b32 == lo) if lo == hi else ((b32 >= lo) & (b32 <= hi))
        ok = part if ok is None else (ok | part)
    return ok


# ---------------------------------------------------------------------------
# Escaped-quote decoding (round 18, ROADMAP direction 5).  Apache's
# ap_escape_logitem writes `\"` for a quote inside a quoted field (%r /
# %{User-Agent}i ...) and `\\` for a backslash, so in a well-formed log a
# DATA quote always sits behind an odd-length backslash run and a field
# TERMINATOR behind an even one.  The reference regex is escape-UNAWARE
# (FORMAT_STRING is a bare lazy `.*?`): it accepts these lines through
# backtracking and delivers the span VERBATIM, backslashes included
# (httpd/utils_apache.py replicates the upstream bug that keeps the
# decode dormant).  The device split therefore models the terminator
# choice, not a byte rewrite: a quote-led separator occurrence whose
# quote has odd backslash parity is masked out of the cursor search.
#
# Soundness (device-valid must imply byte-identity with the host):
# - FINAL op (the format's last separator, host rest is `$`): masking is
#   unconditionally exact.  The host's lazy scan tries occurrences in
#   order and only an occurrence ENDING the line can satisfy the end
#   anchor; every masked (odd-parity) occurrence the device skipped lies
#   strictly before its chosen terminator, hence before line end, hence
#   the host rejects it too and lands on the same terminator.
# - NON-final op: the host might match at a skipped occurrence (its rest
#   is a full regex tail, satisfiable by hostile bytes), and proving it
#   cannot requires evaluating that tail.  Such lines are NOT claimed:
#   any skipped occurrence before the chosen terminator invalidates the
#   line and routes it to the oracle, which applies the reference's
#   backtracking exactly.  (Realistic escaped quotes inside %r/referer
#   rarely form a separator occurrence at all — `\"x` contains no
#   `" `/`" "` — so the conservative arm costs only genuinely ambiguous
#   lines, which also failed the device split before this round.)
#
# Plausibility is untouched: the host regex is escape-unaware, so the
# UNMASKED occurrence masks remain the sound model (regex-accept still
# implies plausible).
# ---------------------------------------------------------------------------

_BACKSLASH = 0x5C


def esc_quote_op_flags(program: DeviceProgram) -> Dict[int, bool]:
    """{op position: op is the program's final op} for every until_lit
    whose separator begins with a quote over an unconstrained (CS_ANY)
    capture — the quoted-field shape escape-parity masking applies to."""
    ops = program.ops
    return {
        i: i == len(ops) - 1
        for i, op in enumerate(ops)
        if op.kind == "until_lit"
        and op.lit[:1] == b'"'
        and op.charset == CS_ANY
    }


def escaped_lead_positions(b32: jnp.ndarray) -> jnp.ndarray:
    """[B, L] bool: the maximal backslash run immediately before position
    p has ODD length — a quote AT p is escaped data under Apache's
    ap_escape_logitem convention, not a field terminator.  One vectorized
    O(B*L) pass (compare + running max), independent of the byte at p;
    zero-padding past line end breaks runs, so no lengths mask is
    needed."""
    B, L = b32.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    non_bs = b32 != _BACKSLASH
    last_non_bs = jax.lax.cummax(
        jnp.where(non_bs, pos, -1), axis=1
    )
    prev_last = jnp.concatenate(
        [jnp.full((B, 1), -1, dtype=jnp.int32), last_non_bs[:, :-1]],
        axis=1,
    )
    run_before = (pos - 1) - prev_last
    return (run_before & 1) == 1


def compute_split_dense(
    program: DeviceProgram,
    b32: jnp.ndarray,
    lengths: jnp.ndarray,
    need_plausible: bool = False,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray], jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Run the split program over int32 byte rows.

    Returns (start_list, end_list, valid, plausible, esc_hit): per-token
    [B] cursors plus the per-line validity mask.  Gather-free: precomputed
    literal-match masks and charset masks + masked reductions.

    ``esc_hit`` (None for programs without a quoted-field op) marks lines
    whose quoted-field cursor search skipped a backslash-escaped separator
    occurrence under the escape-parity mask (see the module comment above
    this function) — on a line that stays valid, the device decoded an
    escaped quote the pre-round-18 split would have rejected.

    ``plausible`` (only when need_plausible) is a SOUND over-approximation of
    "the format's real regex could accept this line": all literal separators
    occur in order (greedy first-occurrence matching is exact for subsequence
    existence, so regex-accept implies plausible; valid implies plausible).
    Multi-format winner selection uses it to avoid claiming a line for format
    k when an earlier format j < k — whose non-backtracking device automaton
    false-rejected the line — might still accept it: such lines go to the
    host oracle, which applies the reference's registration-priority
    semantics exactly."""
    B, L = b32.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1)
    cursor = jnp.zeros(B, dtype=jnp.int32)
    valid = jnp.ones(B, dtype=bool)
    n_tok = len(program.tokens)
    zeros = jnp.zeros(B, dtype=jnp.int32)
    starts: List[jnp.ndarray] = [zeros] * n_tok
    ends: List[jnp.ndarray] = [zeros] * n_tok

    # Literal-match masks for every distinct separator, computed once: full
    # literal matches starting at this position AND fits inside the line.
    lit_masks: Dict[bytes, jnp.ndarray] = {}
    for lit in sorted({op.lit for op in program.ops if op.lit}):
        m = None
        for k, byte in enumerate(lit):
            part = shift_zero(b32, k) == byte if k else (b32 == byte)
            m = part if m is None else (m & part)
        lit_masks[lit] = m & (pos + len(lit) <= lengths[:, None])

    cs_masks = {
        name: _charset_mask(b32, program.charset_table[cid])
        for name, cid in program.charset_ids.items()
    }

    esc_ops = esc_quote_op_flags(program)
    esc_mask = escaped_lead_positions(b32) if esc_ops else None
    esc_hit = jnp.zeros(B, dtype=bool) if esc_ops else None

    def check_charset(start, end, op, valid):
        cs_ok = cs_masks[op.charset]
        outside = (pos < start[:, None]) | (pos >= end[:, None])
        span_ok = jnp.all(cs_ok | outside, axis=1)
        width = end - start
        # CLF alternations ('number|-'): a lone '-' is legal even though the
        # charset also admits digits; min_len floor of 1 covers both arms.
        ok = valid & span_ok & (width >= op.min_len)
        if op.max_len:
            # Fixed/bounded-width regexes (e.g. '.' for $pipe matches ONE
            # byte): without this the device accepts longer spans the real
            # regex rejects — silently diverging instead of falling back.
            ok = ok & (width <= op.max_len)
        return ok

    # Plausibility: chase each separator's FIRST occurrence at/after a free
    # cursor — subsequence existence, for which greedy first-occurrence
    # matching is exact — with three additional SOUND anchorings (each is a
    # consequence of regex acceptance, so regex-accept still implies
    # plausible): (a) a leading literal must match at position 0 (the regex
    # is ^-anchored); (b) the final literal must end exactly at the line end
    # ($-anchored); (c) when the last token is to_end with a bounded
    # charset, the preceding separator must sit past the last
    # charset-violating byte.  (b)/(c) keep e.g. `common` from looking
    # plausible on every `combined` line (spaces occur everywhere), which
    # would otherwise send all those lines to the oracle.
    plausible = None
    if need_plausible:
        ops_list = list(program.ops)
        plausible = jnp.ones(B, dtype=bool)
        p_cursor = jnp.zeros(B, dtype=jnp.int32)
        for idx, op in enumerate(ops_list):
            if not op.lit:
                continue  # to_end: handled via the preceding separator
            k = len(op.lit)
            is_first = idx == 0 and op.kind == "lit"
            remaining = ops_list[idx + 1 :]
            is_final_sep = not any(o.lit for o in remaining)
            usable = lit_masks[op.lit]
            if is_first:
                usable = usable & (pos == 0)
            else:
                usable = usable & (pos >= p_cursor[:, None])
            if is_final_sep and not remaining:
                # Trailing separator: the regex is end-anchored.
                usable = usable & (pos == lengths[:, None] - k)
            elif is_final_sep and remaining[0].kind == "to_end":
                tail = remaining[0]
                if tail.charset != CS_ANY and not tail.narrow:
                    # A NARROW charset under-approximates the regex's set,
                    # so it must not anchor plausibility (regex-accept
                    # must still imply plausible).
                    # The to_end token spans [q + k, length); it can only
                    # satisfy its charset if q + k is past the last
                    # violating byte.
                    bad = ~cs_masks[tail.charset] & (pos < lengths[:, None])
                    last_bad = jnp.max(
                        jnp.where(bad, pos, -1), axis=1
                    ).astype(jnp.int32)
                    usable = usable & (pos >= (last_bad - k + 1)[:, None])
                # until_lit final sep followed by to_end cannot happen (the
                # separator belongs to until_lit and to_end has none), so q
                # need not sit at line end here.
            found = jnp.min(jnp.where(usable, pos, L), axis=1).astype(jnp.int32)
            plausible = plausible & (found < L)
            p_cursor = found + k

    for oi, op in enumerate(program.ops):
        if op.kind == "lit":
            # Literal matches exactly at the cursor: probe the match mask
            # with a one-hot reduction (no gather).
            ok = jnp.any(lit_masks[op.lit] & (pos == cursor[:, None]), axis=1)
            valid = valid & ok
            cursor = cursor + len(op.lit)
        elif op.kind == "until_lit":
            usable = lit_masks[op.lit] & (pos >= cursor[:, None])
            if oi in esc_ops:
                # Escape-parity mask: an occurrence whose quote sits
                # behind an odd backslash run is data, not a terminator.
                skipped = usable & esc_mask
                usable = usable & ~esc_mask
                first_skip = jnp.min(
                    jnp.where(skipped, pos, L), axis=1
                ).astype(jnp.int32)
            found = jnp.min(jnp.where(usable, pos, L), axis=1).astype(jnp.int32)
            if oi in esc_ops:
                had_skip = first_skip < found
                if esc_ops[oi]:
                    # Final op: skipping is exact (host rest is `$`).
                    esc_hit = esc_hit | had_skip
                else:
                    # Non-final op: the host might match at the skipped
                    # occurrence — don't claim, let the oracle decide.
                    valid = valid & ~had_skip
            token_valid = found < L
            start = cursor
            end = jnp.where(token_valid, found, cursor)
            valid = check_charset(start, end, op, valid & token_valid)
            starts[op.token_index] = start
            ends[op.token_index] = end
            cursor = end + len(op.lit)
        elif op.kind == "to_end":
            start = cursor
            end = lengths
            valid = check_charset(start, end, op, valid)
            starts[op.token_index] = start
            ends[op.token_index] = end
            cursor = end
        else:  # pragma: no cover
            raise AssertionError(op.kind)

    # The whole line must be consumed (the regex is end-anchored).
    valid = valid & (cursor == lengths)
    return starts, ends, valid, plausible, esc_hit


# ---------------------------------------------------------------------------
# Bitplane split executor.  The dense splitter above costs one full [B, L]
# reduction pass PER op (each until_lit first-occurrence search and each
# charset span check reads the whole buffer again); the sequential cursor
# dependency keeps XLA from fusing the passes, so ~14 passes dominated the
# round-3 kernel profile (ROADMAP item 1).  The bitplane form packs the
# buffer ONCE into per-byte-class position bitplanes — [B, C] uint32 words,
# C = ceil(L/32), bit j of word c = "class matches at position c*32+j" —
# and then every search, literal probe, charset span check and plausibility
# anchoring runs on the planes with word arithmetic (shift/AND/popcount +
# tiny reductions over C).  One O(B*L) pass total instead of ~14.
#
# Exactness: multi-byte literal occurrence masks are derived from the
# single-byte planes with cross-word shifts, and every resolution below
# reproduces compute_split_dense bit-for-bit (locked by
# tests/test_bitplane_split.py differential sweeps).
# ---------------------------------------------------------------------------

_PLANE_W = 32
_PLANE_FULL = np.uint32(0xFFFFFFFF)


def _plane_pack(pred: jnp.ndarray, C: int) -> jnp.ndarray:
    """[B, C*32] bool -> [B, C] uint32 position bitplane."""
    B = pred.shape[0]
    w = pred.reshape(B, C, _PLANE_W)
    weights = jnp.uint32(1) << jnp.arange(_PLANE_W, dtype=jnp.uint32)
    return jnp.sum(
        jnp.where(w, weights, jnp.uint32(0)), axis=2, dtype=jnp.uint32
    )


def _plane_shr(plane: jnp.ndarray, k: int) -> jnp.ndarray:
    """Bit p of the result = bit p+k of the input (cross-word carry).

    Arbitrary k: whole words shift as column moves, the remainder as a
    bit shift (k is the literal byte offset, so separators longer than
    one 32-bit word still derive correctly)."""
    wshift, bshift = divmod(k, _PLANE_W)
    if wshift:
        plane = jnp.pad(plane[:, wshift:], ((0, 0), (0, wshift)))
    if bshift:
        nxt = jnp.pad(plane[:, 1:], ((0, 0), (0, 1)))
        plane = (plane >> jnp.uint32(bshift)) | (
            nxt << jnp.uint32(_PLANE_W - bshift)
        )
    return plane


def _plane_cutoff(thresh: jnp.ndarray, C: int) -> jnp.ndarray:
    """[B] threshold -> [B, C] plane with bits set at positions < thresh."""
    word_idx = jnp.arange(C, dtype=jnp.int32)[None, :]
    rel = jnp.clip(thresh[:, None] - word_idx * _PLANE_W, 0, _PLANE_W)
    partial = (jnp.uint32(1) << rel.astype(jnp.uint32)) - jnp.uint32(1)
    return jnp.where(rel >= _PLANE_W, _PLANE_FULL, partial)


def _plane_word_at(plane: jnp.ndarray, wi: jnp.ndarray, C: int) -> jnp.ndarray:
    """Select word wi per row (one-hot sum; out-of-range -> 0)."""
    idx = jnp.arange(C, dtype=jnp.int32)[None, :]
    return jnp.sum(
        jnp.where(idx == wi[:, None], plane, jnp.uint32(0)),
        axis=1, dtype=jnp.uint32,
    )


def _plane_first_ge(
    plane: jnp.ndarray, cursor: jnp.ndarray, C: int, L: int
) -> jnp.ndarray:
    """First set-bit position >= cursor per row; L when none."""
    cw = cursor // _PLANE_W
    cb = (cursor % _PLANE_W).astype(jnp.uint32)
    idx = jnp.arange(C, dtype=jnp.int32)[None, :]
    tail = _PLANE_FULL << cb[:, None]
    keep = jnp.where(
        idx == cw[:, None], plane & tail,
        jnp.where(idx > cw[:, None], plane, jnp.uint32(0)),
    )
    nz = keep != 0
    first_w = jnp.min(jnp.where(nz, idx, C), axis=1)
    word = _plane_word_at(keep, first_w, C)
    low = word & (jnp.uint32(0) - word)
    bit = jax.lax.population_count(low - jnp.uint32(1))
    found = first_w * _PLANE_W + bit.astype(jnp.int32)
    return jnp.where(word != 0, found, L)


def _plane_test_bit(plane: jnp.ndarray, p: jnp.ndarray, C: int) -> jnp.ndarray:
    """Bit test at position p per row (out-of-range -> False)."""
    word = _plane_word_at(plane, p // _PLANE_W, C)
    bit = (word >> (p % _PLANE_W).astype(jnp.uint32)) & jnp.uint32(1)
    return (bit != 0) & (p >= 0) & (p < C * _PLANE_W)


def _plane_any_in_range(
    plane: jnp.ndarray, start: jnp.ndarray, end: jnp.ndarray, C: int
) -> jnp.ndarray:
    """Any set bit at a position in [start, end) per row."""
    rng = _plane_cutoff(end, C) & ~_plane_cutoff(start, C)
    return jnp.any((plane & rng) != 0, axis=1)


def _plane_last_set(plane: jnp.ndarray, C: int) -> jnp.ndarray:
    """Highest set-bit position per row; -1 when the plane is empty."""
    idx = jnp.arange(C, dtype=jnp.int32)[None, :]
    nz = plane != 0
    last_w = jnp.max(jnp.where(nz, idx, -1), axis=1)
    word = _plane_word_at(plane, last_w, C)
    w = word
    for s in (1, 2, 4, 8, 16):
        w = w | (w >> jnp.uint32(s))
    high = jax.lax.population_count(w).astype(jnp.int32) - 1
    return jnp.where(last_w >= 0, last_w * _PLANE_W + high, -1)


def compute_split(
    program: DeviceProgram,
    b32: jnp.ndarray,
    lengths: jnp.ndarray,
    need_plausible: bool = False,
) -> Tuple[List[jnp.ndarray], List[jnp.ndarray], jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Bitplane execution of the split program — semantically identical to
    :func:`compute_split_dense` (same return contract; see its docstring for
    the plausibility soundness argument and the escape-parity module
    comment for ``esc_hit``), one O(B*L) packing pass total."""
    if any(0 in op.lit for op in program.ops if op.lit):
        # A NUL byte inside a separator literal would collide with the
        # zero padding the plane derivation relies on.
        return compute_split_dense(program, b32, lengths, need_plausible)
    B, L = b32.shape
    C = -(-L // _PLANE_W)
    Lp = C * _PLANE_W
    bp = jnp.pad(b32, ((0, 0), (0, Lp - L))) if Lp != L else b32

    lit_bytes = sorted({bt for op in program.ops if op.lit for bt in op.lit})
    charsets = sorted({
        op.charset for op in program.ops
        if op.kind != "lit" and op.charset != CS_ANY
    })
    byte_planes = {bt: _plane_pack(bp == bt, C) for bt in lit_bytes}
    viol_planes = {
        cs: _plane_pack(
            ~_charset_mask(bp, program.charset_table[program.charset_ids[cs]]),
            C,
        )
        for cs in charsets
    }
    lit_planes: Dict[bytes, jnp.ndarray] = {}
    for lit in sorted({op.lit for op in program.ops if op.lit}):
        m = byte_planes[lit[0]]
        for k, bt in enumerate(lit[1:], 1):
            m = m & _plane_shr(byte_planes[bt], k)
        # Same guard as the dense lit_masks: the occurrence must fit
        # inside the line (pos + len(lit) <= lengths).
        lit_planes[lit] = m & _plane_cutoff(lengths - (len(lit) - 1), C)

    esc_ops = esc_quote_op_flags(program)
    esc_plane = (
        _plane_pack(escaped_lead_positions(bp), C) if esc_ops else None
    )

    zeros = jnp.zeros(B, dtype=jnp.int32)
    cursor = zeros
    valid = jnp.ones(B, dtype=bool)
    esc_hit = jnp.zeros(B, dtype=bool) if esc_ops else None
    n_tok = len(program.tokens)
    starts: List[jnp.ndarray] = [zeros] * n_tok
    ends: List[jnp.ndarray] = [zeros] * n_tok

    def check_charset(start, end, op, valid):
        if op.charset != CS_ANY:
            bad = _plane_any_in_range(viol_planes[op.charset], start, end, C)
            valid = valid & ~bad
        width = end - start
        ok = valid & (width >= op.min_len)
        if op.max_len:
            ok = ok & (width <= op.max_len)
        return ok

    for oi, op in enumerate(program.ops):
        if op.kind == "lit":
            ok = _plane_test_bit(lit_planes[op.lit], cursor, C)
            valid = valid & ok
            cursor = cursor + len(op.lit)
        elif op.kind == "until_lit":
            if oi in esc_ops:
                # Escape-parity mask (see the dense variant): search the
                # even-parity plane; a skipped odd-parity occurrence is
                # exact for the final op, un-claims the line otherwise.
                found = _plane_first_ge(
                    lit_planes[op.lit] & ~esc_plane, cursor, C, L
                )
                first_skip = _plane_first_ge(
                    lit_planes[op.lit] & esc_plane, cursor, C, L
                )
                had_skip = first_skip < found
                if esc_ops[oi]:
                    esc_hit = esc_hit | had_skip
                else:
                    valid = valid & ~had_skip
            else:
                found = _plane_first_ge(lit_planes[op.lit], cursor, C, L)
            token_valid = found < L
            start = cursor
            end = jnp.where(token_valid, found, cursor)
            valid = check_charset(start, end, op, valid & token_valid)
            starts[op.token_index] = start
            ends[op.token_index] = end
            cursor = end + len(op.lit)
        elif op.kind == "to_end":
            start = cursor
            end = lengths
            valid = check_charset(start, end, op, valid)
            starts[op.token_index] = start
            ends[op.token_index] = end
            cursor = end
        else:  # pragma: no cover
            raise AssertionError(op.kind)
    valid = valid & (cursor == lengths)

    plausible = None
    if need_plausible:
        # Same chase as compute_split_dense (see its inline comments for
        # the soundness of each anchoring), resolved on the planes.
        ops_list = list(program.ops)
        plausible = jnp.ones(B, dtype=bool)
        p_cursor = zeros
        for idx, op in enumerate(ops_list):
            if not op.lit:
                continue
            k = len(op.lit)
            is_first = idx == 0 and op.kind == "lit"
            remaining = ops_list[idx + 1:]
            is_final_sep = not any(o.lit for o in remaining)
            plane = lit_planes[op.lit]
            lower = p_cursor
            exact: Optional[jnp.ndarray] = None
            if is_first:
                exact = zeros
            if is_final_sep and not remaining:
                e2 = lengths - k
                exact = e2 if exact is None else jnp.where(
                    exact == e2, exact, jnp.full(B, -1, jnp.int32)
                )
            elif is_final_sep and remaining[0].kind == "to_end":
                tail = remaining[0]
                if tail.charset != CS_ANY and not tail.narrow:
                    masked = (
                        viol_planes[tail.charset]
                        & _plane_cutoff(lengths, C)
                    )
                    last_bad = _plane_last_set(masked, C)
                    lower = jnp.maximum(lower, last_bad - k + 1)
            if exact is not None:
                hit = _plane_test_bit(plane, exact, C) & (exact >= lower)
                found = jnp.where(hit, exact, L)
            else:
                found = _plane_first_ge(plane, lower, C, L)
            plausible = plausible & (found < L)
            p_cursor = found + k
    return starts, ends, valid, plausible, esc_hit


# ---------------------------------------------------------------------------
# Packed output layout: every output component is a bit slot (row, shift,
# bits) in the [K, B] int32 result.  Span-producing kinds pack
# start|len|ok into ONE row (13+13+1 bits; L is capped at 8191 =
# runtime.DEFAULT_MAX_LINE_LEN); numeric/epoch aux bits (ok/null/lo_digits)
# share trailing "meta" rows.  Every row is D2H bytes per line, so rows
# are precious.
# ---------------------------------------------------------------------------

_SPAN_BITS = 13          # start / len each; supports L up to 8191

Slot = Tuple[int, int, int]   # (row, shift, bits); bits=0 -> full int32 row


def ts_group_key(plan: FieldPlan) -> str:
    """All ts plans over the same token+steps share one component bundle."""
    return f"@ts:{plan.token_index}:{plan.steps!r}"


# Default segment slots per CSR wildcard split (query params / cookies).
# Lines with more segments than slots are routed to the oracle AND flagged
# in the validity row (CSR_OVERFLOW_BIT); TpuBatchParser reacts by doubling
# the layout's slot count (up to CSR_SLOTS_MAX) and re-running the batch, so
# query-heavy corpora pay a bounded number of recompiles instead of a
# per-line oracle cliff.
CSR_SLOTS = 16
CSR_SLOTS_MAX = 128

# CSR scan-window budget, in span bytes per segment slot.  split_csr runs
# its scans over a compact [B, slots * CSR_WINDOW_PER_SLOT] window cut
# out of the span by the log-shift alignment, instead of the full padded
# line — spans (query strings, cookie headers) are tiny next to L, and
# the scans are the kernel's dominant cost.  A span longer than the
# window raises the same CSR_OVERFLOW_BIT as running out of slots, and
# the same adaptive response (double the slots, window scales along)
# resolves it; at CSR_SLOTS_MAX the window covers 1024 bytes and longer
# spans stay oracle-bound, exactly like slot exhaustion.
CSR_WINDOW_PER_SLOT = 8

# Scan window of the URI fast split (path + query + authority in one
# span), in bytes.  A line bucket of up to this many bytes is split
# unwindowed (no URI can overflow it); a longer bucket scans this
# window and routes the rare longer URI to the batched rescue.  Fixed,
# not grown with the CSR slots: a growth rebuilds the executor, and the
# first long URI would then compile every warm shape again on the
# request path.
URI_WINDOW = 1536

# row 0 bit assignments (see compute_rows): bit 0 = line validity, bit 1 =
# plausibility (multi-format winner protocol), bit 2 = CSR slot overflow,
# bit 3 = the valid line's quoted-field split consumed a backslash-escaped
# separator occurrence (escape-parity masking — the device handled a line
# that pre-round-18 routed to the host rescue).
CSR_OVERFLOW_BIT = 4
ESC_QUOTE_BIT = 8


def csr_group_key(plan: FieldPlan) -> str:
    """All qscsr plans over the same token+steps+mode share one segment
    table (mode — query vs cookie — picks the separator)."""
    return f"@qs:{plan.token_index}:{plan.meta}:{plan.steps!r}"


_CSR_SEPARATORS = {"query": b"&", "cookie": b"; "}


def geo_group_key(plan: FieldPlan) -> str:
    """All geo plans over the same token+steps+database share one device
    range-join (plan.meta = (tag, column, GeoDeviceTable); the tag is the
    pickle-stable database identity)."""
    return f"@geo:{plan.token_index}:{plan.meta[0]}:{plan.steps!r}"


def muid_group_key(plan: FieldPlan) -> str:
    """All mod_unique_id plans over the same token+steps share one decode."""
    return f"@muid:{plan.token_index}:{plan.steps!r}"


@dataclass
class PackedLayout:
    """Bit-slot map for the packed [K, B] int32 output (row 0 = validity).

    Timestamp component bundles are shared: every ``ts`` plan on the same
    (token, steps) maps to one ``@ts:...`` slot group with rows
    ``c1`` (year|month|day|hour), ``c2`` (minute|second|milli), ``off``
    (raw UTC offset seconds) and an ``ok`` bit.
    """

    slots: Dict[str, Dict[str, Slot]] = dataclass_field(default_factory=dict)
    n_rows: int = 1
    csr_slots: int = CSR_SLOTS

    @classmethod
    def for_plans(
        cls, plans: Sequence[FieldPlan], csr_slots: int = CSR_SLOTS
    ) -> "PackedLayout":
        layout = cls(csr_slots=csr_slots)
        aux_needs: List[Tuple[str, str, int]] = []  # (slot_key, comp, bits)
        for plan in plans:
            kind = plan.kind
            if kind == "host":
                continue
            if kind in ("span", "ulist"):
                r = layout.n_rows
                layout.n_rows += 1
                layout.slots[plan.field_id] = {
                    "start": (r, 0, _SPAN_BITS),
                    "len": (r, _SPAN_BITS, _SPAN_BITS),
                    "ok": (r, 2 * _SPAN_BITS, 1),
                    # null: the value is absent/None (CLF '-' on direct
                    # token captures; undelivered URI parts).  amp: the
                    # span's leading '?' renders as '&' (query
                    # normalization).  fix: the row needs per-row host
                    # micro-materialization (%-repair / path decode).
                    "null": (r, 2 * _SPAN_BITS + 1, 1),
                    "amp": (r, 2 * _SPAN_BITS + 2, 1),
                    "fix": (r, 2 * _SPAN_BITS + 3, 1),
                }
            elif kind in ("long", "secmillis"):
                rhi, rlo = layout.n_rows, layout.n_rows + 1
                layout.n_rows += 2
                layout.slots[plan.field_id] = {
                    "hi": (rhi, 0, 0),
                    "lo": (rlo, 0, 0),
                }
                aux_needs += [
                    (plan.field_id, "ok", 1),
                    (plan.field_id, "null", 1),
                    (plan.field_id, "lo_digits", 5),  # digit count <= 19
                    (plan.field_id, "d18", 4),        # the 19th frame digit
                    # >19-digit run, device-valid: the hi row carries
                    # start|len<<_SPAN_BITS for the host byte-patch.
                    (plan.field_id, "big", 1),
                ]
                if kind == "secmillis":
                    aux_needs.append((plan.field_id, "milli", 10))
            elif kind == "ts":
                key = ts_group_key(plan)
                if key not in layout.slots:
                    r = layout.n_rows
                    layout.n_rows += 3
                    layout.slots[key] = {
                        "c1": (r, 0, 0),
                        "c2": (r + 1, 0, 0),
                        "off": (r + 2, 0, 0),
                    }
                    aux_needs.append((key, "ok", 1))
            elif kind == "geo":
                key = geo_group_key(plan)
                if key not in layout.slots:
                    r = layout.n_rows
                    layout.n_rows += 1
                    layout.slots[key] = {"row": (r, 0, 0)}
                    aux_needs.append((key, "ok", 1))
            elif kind == "muid":
                key = muid_group_key(plan)
                if key not in layout.slots:
                    r = layout.n_rows
                    layout.n_rows += 4
                    layout.slots[key] = {
                        "time": (r, 0, 0),
                        "ip": (r + 1, 0, 0),
                        "pid": (r + 2, 0, 0),
                        "thread": (r + 3, 0, 0),
                    }
                    aux_needs += [(key, "ok", 1), (key, "counter", 16)]
            elif kind == "qscsr":
                key = csr_group_key(plan)
                if key not in layout.slots:
                    slots: Dict[str, Slot] = {}
                    for k in range(csr_slots):
                        rn = layout.n_rows
                        rv = layout.n_rows + 1
                        layout.n_rows += 2
                        slots[f"s{k}_start"] = (rn, 0, _SPAN_BITS)
                        slots[f"s{k}_nlen"] = (rn, _SPAN_BITS, _SPAN_BITS)
                        slots[f"s{k}_eq"] = (rn, 2 * _SPAN_BITS, 1)
                        slots[f"s{k}_dec"] = (rn, 2 * _SPAN_BITS + 1, 1)
                        slots[f"s{k}_ndec"] = (rn, 2 * _SPAN_BITS + 2, 1)
                        slots[f"s{k}_nhigh"] = (rn, 2 * _SPAN_BITS + 3, 1)
                        slots[f"s{k}_vstart"] = (rv, 0, _SPAN_BITS)
                        slots[f"s{k}_vlen"] = (rv, _SPAN_BITS, _SPAN_BITS)
                    layout.slots[key] = slots
                    aux_needs.append((key, "ok", 1))
            else:  # pragma: no cover
                raise AssertionError(kind)
        # Pack aux bits into shared meta rows (30 usable bits per row: the
        # sign bit stays clear and decoding needs no sign games).
        shift = 30
        row = layout.n_rows - 1
        for fid, comp, bits in aux_needs:
            if shift + bits > 30:
                row = layout.n_rows
                layout.n_rows += 1
                shift = 0
            layout.slots.setdefault(fid, {})[comp] = (row, shift, bits)
            shift += bits
        return layout

    # -- host-side decode ------------------------------------------------

    def get(self, packed: np.ndarray, field_id: str, comp: str) -> np.ndarray:
        """Decode one component from the packed [K, B] host array."""
        row, shift, bits = self.slots[field_id][comp]
        col = packed[row]
        if bits == 0:
            return col
        return (col >> shift) & ((1 << bits) - 1)

    def get_ts_components(self, packed: np.ndarray, plan: FieldPlan):
        """Decode a ts plan's shared component bundle -> (components, ok).

        Bit layout written by compute_rows: c1 = year | month<<14 | day<<18
        | hour<<23; c2 = minute | second<<6 | milli<<12; off = raw int32.
        """
        key = ts_group_key(plan)
        c1 = self.get(packed, key, "c1")
        c2 = self.get(packed, key, "c2")
        comp = {
            "year": (c1 & 0x3FFF).astype(np.int64),
            "month": ((c1 >> 14) & 0xF).astype(np.int64),
            "day": ((c1 >> 18) & 0x1F).astype(np.int64),
            "hour": ((c1 >> 23) & 0x1F).astype(np.int64),
            "minute": (c2 & 0x3F).astype(np.int64),
            "second": ((c2 >> 6) & 0x3F).astype(np.int64),
            "milli": ((c2 >> 12) & 0x3FF).astype(np.int64),
            "offset_seconds": self.get(packed, key, "off").astype(np.int64),
        }
        ok = self.get(packed, key, "ok") != 0
        return comp, ok


def span_prefix_words(
    b32: jnp.ndarray,
    s: jnp.ndarray,
    e: jnp.ndarray,
    ok: jnp.ndarray,
    null: Optional[jnp.ndarray],
    amp: Optional[jnp.ndarray],
    extract,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """LE-packed first-12-byte words of one span field, computed IN the
    unit pass (bytes masked beyond len; dead rows all-zero).  Gathering
    here — where the split/chain stages are already streaming the byte
    buffer — folds the view-prefix extraction into the same fusion
    cluster; the pre-round-6 post-merge gather depended on every unit's
    packed rows, so XLA had to re-stream the whole [B, L] buffer in a
    separate HBM sweep per view field.  The '?'->'&' query normalization
    is rendered in place so <= 12-byte amp values need no host patching."""
    length = e - s
    live = ok if null is None else (ok & ~null)
    first12 = extract(b32, s, 12)
    pos = jnp.arange(12, dtype=jnp.int32)[None, :]
    masked = jnp.where(
        live[:, None] & (pos < length[:, None]),
        first12.astype(jnp.int32),
        0,
    )
    if amp is not None:
        amp_row = amp & live & (length > 0) & (masked[:, 0] == ord("?"))
        masked = masked.at[:, 0].set(
            jnp.where(amp_row, ord("&"), masked[:, 0])
        )
    words = []
    for w in range(3):
        b = masked[:, 4 * w: 4 * w + 4]
        words.append(
            (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16)
             | (b[:, 3] << 24)).astype(jnp.int32)
        )
    return words[0], words[1], words[2]


def compute_rows(
    program: DeviceProgram,
    plans: Sequence[FieldPlan],
    layout: PackedLayout,
    b32: jnp.ndarray,
    lengths: jnp.ndarray,
    need_plausible: bool = False,
    view_fields: Sequence[str] = (),
) -> Tuple[List[jnp.ndarray], Dict[str, Tuple[jnp.ndarray, ...]]]:
    """The fused computation: split + per-plan post-stages -> K rows of [B]
    int32 (row 0: bit 0 = line validity, bit 1 = plausibility when
    requested).  Returns (rows, view_prefix): the executor stacks the
    rows; ``view_prefix`` maps each requested ``view_fields`` span field
    to its 3 LE-packed first-12-byte words (see span_prefix_words),
    consumed by the winner merge in :func:`compute_view_rows`."""
    B = b32.shape[0]
    starts, ends, valid, plausible, esc_hit = compute_split(
        program, b32, lengths, need_plausible
    )
    extract = postproc.gather_span_bytes

    rows: List[Optional[jnp.ndarray]] = [None] * layout.n_rows
    view_set = frozenset(view_fields)
    view_prefix: Dict[str, Tuple[jnp.ndarray, ...]] = {}

    def put(fid: str, comp: str, val: jnp.ndarray) -> None:
        row, shift, bits = layout.slots[fid][comp]
        v = val.astype(jnp.int32)
        if bits:
            v = (v & ((1 << bits) - 1)) << shift
        rows[row] = v if rows[row] is None else (rows[row] | v)

    def put_span(fid: str, s, e, ok, null=None, amp=None, fix=None) -> None:
        put(fid, "start", s)
        put(fid, "len", e - s)
        put(fid, "ok", jnp.where(ok, 1, 0))
        if null is not None:
            put(fid, "null", jnp.where(null, 1, 0))
        if amp is not None:
            put(fid, "amp", jnp.where(amp, 1, 0))
        if fix is not None:
            put(fid, "fix", jnp.where(fix, 1, 0))
        if fid in view_set:
            view_prefix[fid] = span_prefix_words(
                b32, s, e, ok, null, amp, extract
            )

    # ---- span-transform chains (device sub-dissectors) ----------------
    # chain(token, steps) -> (start, end, ok, null, amp); each prefix is
    # computed once.  Steps may also constrain LINE validity (a URI the
    # repair chain would rewrite must send the whole line to the oracle,
    # which re-applies the exact repair semantics).
    fl_cache: Dict[tuple, Dict[str, jnp.ndarray]] = {}
    uri_cache: Dict[tuple, Dict[str, jnp.ndarray]] = {}
    pv_cache: Dict[tuple, Dict[str, jnp.ndarray]] = {}
    chain_cache: Dict[tuple, tuple] = {}
    line_constraints: List[jnp.ndarray] = []
    csr_overflow_rows: List[jnp.ndarray] = []
    false_b = jnp.zeros(B, dtype=bool)
    # Authority reductions (userinfo/host/port) only run when some plan
    # actually delivers those parts — path/query-only workloads skip them.
    need_authority = any(
        ("uri", part) in plan.steps
        for plan in plans
        for part in ("host", "userinfo", "port")
    )

    def clf_dash(s, e):
        """Token-level CLF null: the span is a lone '-'
        (decode_extracted_value, ApacheHttpdLogFormatDissector:176-178 /
        NginxHttpdLogFormatDissector:107-119)."""
        first = extract(b32, s, 1)[:, 0]
        return ((e - s) == 1) & (first == np.uint8(ord("-")))

    def run_step(step: Tuple[str, str], s, e, ok, cache_key):
        name, part = step
        if name == "fl":
            fl = fl_cache.get(cache_key)
            if fl is None:
                fl = postproc.split_firstline(
                    b32, lengths, s, e, extract=extract
                )
                fl_cache[cache_key] = fl
            if part == "protocol":
                step_ok = fl["ok"] & fl["has_protocol"]
                return (fl["proto_start"], fl["proto_end"], ok & step_ok,
                        false_b, false_b, false_b)
            return (
                fl[f"{part}_start"], fl[f"{part}_end"], ok & fl["ok"],
                false_b, false_b, false_b,
            )
        if name == "pv":
            pv = pv_cache.get(cache_key)
            if pv is None:
                # Direct token input: CLF '-' is null (the dissector's
                # early return).  Sub-spans (firstline protocol) cannot be
                # a lone dash — the fl split already requires "HTTP/".
                dash = clf_dash(s, e) if len(cache_key) == 1 else None
                pv = postproc.split_protocol_version(b32, s, e, dash=dash)
                pv_cache[cache_key] = pv
            if part == "protocol":
                return (s, pv["proto_end"], ok, pv["null"], false_b, false_b)
            return (
                pv["ver_start"], pv["ver_end"], ok, pv["null"],
                false_b, false_b,
            )
        if name == "uri":
            uri = uri_cache.get(cache_key)
            if uri is None:
                # Direct token input: CLF null — the dissector receives
                # None and delivers nothing.  Sub-spans (firstline uri)
                # take '-' literally, like the host.
                dash = clf_dash(s, e) if len(cache_key) == 1 else None
                uri = postproc.split_uri_fast(
                    b32, s, e, extract=extract, dash=dash,
                    need_authority=need_authority,
                    window=URI_WINDOW,
                )
                uri_cache[cache_key] = uri
                # Repair-needing URIs fail the line (unless the chain
                # already produced nothing to repair).
                line_constraints.append(uri["ok"] | ~ok)
                # Span longer than the fixed scan window: fail the line
                # so it rides the batched rescue.  No CSR overflow bit —
                # more slots would not widen this window.
                line_constraints.append(~(uri["overflow"] & ok))
            step_ok = ok & uri["ok"]
            if part == "path":
                return (
                    uri["path_start"], uri["path_end"], step_ok,
                    uri["path_null"], false_b, uri["path_fix"],
                )
            if part == "query":
                return (
                    uri["query_start"], uri["query_end"], step_ok,
                    uri["query_null"], uri["query_amp"], uri["query_fix"],
                )
            if part == "protocol":
                return (
                    uri["proto_start"], uri["proto_end"], step_ok,
                    uri["proto_null"], false_b, false_b,
                )
            if part == "userinfo":
                return (
                    uri["userinfo_start"], uri["userinfo_end"], step_ok,
                    uri["userinfo_null"], false_b, uri["userinfo_fix"],
                )
            if part == "host":
                return (
                    uri["host_start"], uri["host_end"], step_ok,
                    uri["host_null"], false_b, false_b,
                )
            if part == "port":
                # Null port == empty span: the downstream long parse fails
                # on it and the column reads None (the host only delivers
                # port when the authority parse produced one).
                return (
                    uri["port_start"], uri["port_end"], step_ok,
                    false_b, false_b, false_b,
                )
            # ref: clean rows cannot contain '#', so the fragment is
            # always absent -> null span.
            return s, s, step_ok, jnp.ones(B, dtype=bool), false_b, false_b
        raise AssertionError(step)  # pragma: no cover

    def chain_spans(token_index: int, steps):
        key = (token_index, steps)
        got = chain_cache.get(key)
        if got is not None:
            return got
        if steps:
            s, e, ok, _, _, _ = chain_spans(token_index, steps[:-1])
            s, e, ok, null, amp, fix = run_step(
                steps[-1], s, e, ok, key[:1] + steps[:-1]
            )
        else:
            s, e = starts[token_index], ends[token_index]
            ok = jnp.ones(B, dtype=bool)
            null = amp = fix = None
        chain_cache[key] = (s, e, ok, null, amp, fix)
        return s, e, ok, null, amp, fix

    group_done = set()  # emitted shared groups (@ts:/@qs: keys)
    for plan in plans:
        if plan.kind == "host":
            continue
        s, e, chain_ok, null, amp, fix = chain_spans(plan.token_index, plan.steps)
        if plan.kind == "span":
            if not plan.steps:
                null = clf_dash(s, e)  # direct token capture: CLF null
            put_span(plan.field_id, s, e, chain_ok, null, amp, fix)
        elif plan.kind in ("long", "secmillis"):
            big = None
            if plan.kind == "secmillis":
                (hi, lo, d18, lo_digits), milli, is_null, ok = (
                    postproc.parse_secmillis_spans(b32, s, e, extract=extract)
                )
                put(plan.field_id, "milli", milli)
            else:
                (hi, lo, d18, lo_digits), is_null, ok, big = (
                    postproc.parse_long_spans(
                        b32, s, e,
                        clf=plan.null_mode in ("dash_null", "dash_zero"),
                        extract=extract,
                    )
                )
            # Full-int64 overflow handling is only wired for the PLAIN
            # direct-token long (the %b/%D FORMAT_NUMBER class): scaled
            # values, zero_null (string-compared) conversions and chained
            # sub-spans keep their pre-widening behavior — decode failure
            # routes the line to the oracle, whose semantics are exact.
            allow_big = (
                plan.kind == "long"
                and not plan.steps
                and plan.scale == 1
                and plan.null_mode != "zero_null"
            )
            if big is not None and allow_big:
                # Device-valid >19-digit runs: the frame cannot carry the
                # value, so the hi row carries the span instead and the
                # host patches the exact value from the byte buffer
                # (reference Long-overflow semantics; only the first 19
                # bytes were digit-checked — the patch checks the rest).
                blen = jnp.minimum(e - s, (1 << _SPAN_BITS) - 1)
                hi = jnp.where(big, s | (blen << _SPAN_BITS), hi)
                lo = jnp.where(big, 0, lo)
                d18 = jnp.where(big, 0, d18)
                put(plan.field_id, "big", jnp.where(big, 1, 0))
            elif big is not None:
                ok = ok & ~big
                put(plan.field_id, "big", jnp.zeros_like(hi))
            else:
                put(plan.field_id, "big", jnp.zeros_like(hi))
            put(plan.field_id, "hi", hi)
            put(plan.field_id, "lo", lo)
            put(plan.field_id, "d18", d18)
            put(plan.field_id, "lo_digits", lo_digits)
            put(plan.field_id, "ok", jnp.where(ok, 1, 0))
            put(plan.field_id, "null", jnp.where(is_null, 1, 0))
            if not plan.steps:
                # Direct token numerics: the split charset admitted the
                # span, so a decode failure (non-digit window bytes,
                # malformed sec.millis, >19-digit runs outside the
                # allow_big class) is exactly a case the host path types
                # differently or rejects — route the line to the oracle.
                valid = valid & (ok | ~chain_ok)
            if plan.null_mode == "zero_null":
                # ConvertNumberIntoCLF compares the STRING to "0": a span
                # with leading zeros ("00", "007") passes through verbatim
                # on the host, which the int64 column cannot represent —
                # those rows go to the oracle.  After this exclusion,
                # value==0 is exactly span=="0".
                first = extract(b32, s, 1)[:, 0]
                leading_zero = ((e - s) > 1) & (first == np.uint8(ord("0")))
                valid = valid & ~(leading_zero & chain_ok)
        elif plan.kind == "geo":
            key = geo_group_key(plan)
            if key in group_done:
                continue
            group_done.add(key)
            table = plan.meta[2]
            u32, ip_ok, has_colon = postproc.parse_ipv4_spans(
                b32, s, e, extract=extract
            )
            rows_idx = table.lookup_rows(u32)
            put(key, "row", jnp.where(ip_ok & chain_ok, rows_idx, 0))
            put(key, "ok", jnp.where(chain_ok, 1, 0))
            # IPv6 literals: the host DOES look them up in the trie; the
            # flattened device table is IPv4-only, so those lines take the
            # oracle.
            valid = valid & ~(has_colon & chain_ok)
        elif plan.kind == "ulist":
            # Indexed nginx upstream-list element.  The list token's
            # NARROW charset excludes every separator and whitespace byte,
            # so a device-valid row is necessarily a SINGLE untrimmable
            # element: element 0 (value and redirected alike) is the token
            # span itself, any higher index is absent.  Multi-element and
            # redirect lists contain charset-rejected bytes and take the
            # oracle, which indexes them exactly.
            u_idx, _u_which = plan.meta
            u_dash = clf_dash(s, e) if not plan.steps else false_b
            if u_idx == 0:
                put_span(plan.field_id, s, e, chain_ok & ~u_dash)
            else:
                put_span(plan.field_id, s, s, jnp.zeros(B, dtype=bool))
        elif plan.kind == "muid":
            key = muid_group_key(plan)
            if key in group_done:
                continue
            group_done.add(key)
            words, ok = postproc.parse_mod_unique_id(
                b32, s, e, extract=extract
            )
            for comp in ("time", "ip", "pid", "thread"):
                put(key, comp, words[comp])
            put(key, "counter", words["counter"])
            put(key, "ok", jnp.where(ok & chain_ok, 1, 0))
            # A non-decodable token just delivers nothing on the host
            # (no line failure) — `valid` is untouched.
        elif plan.kind == "qscsr":
            key = csr_group_key(plan)
            if key in group_done:
                continue
            group_done.add(key)
            if plan.meta == "setcookie":
                if not plan.steps:
                    chain_ok = chain_ok & ~clf_dash(s, e)
                sc = postproc.split_setcookie_csr(
                    b32, s, e, layout.csr_slots,
                )
                for k in range(layout.csr_slots):
                    seg_s = sc["seg_start"][k]
                    seg_e = sc["seg_end"][k]
                    emit = sc["emit"][k]
                    put(key, f"s{k}_start", jnp.where(emit, seg_s, 0))
                    put(key, f"s{k}_nlen",
                        jnp.where(emit, sc["name_end"][k] - seg_s, 0))
                    put(key, f"s{k}_eq", jnp.where(emit, 1, 0))
                    put(key, f"s{k}_vstart", jnp.where(emit, seg_s, 0))
                    put(key, f"s{k}_vlen", jnp.where(emit, seg_e - seg_s, 0))
                put(key, "ok", jnp.where(chain_ok, 1, 0))
                # Host-quirk rows (overwritten held part, set-cookie:
                # prefix) and slot overflow take the oracle.  The overflow
                # bit is masked by the running line validity: overflow on
                # an already-rejected line must not trigger slot growth.
                valid = valid & ~(sc["bad"] & chain_ok)
                overflowed = sc["overflow"] & chain_ok & valid
                valid = valid & ~overflowed
                csr_overflow_rows.append(overflowed)
                continue
            if plan.steps and plan.steps[-1] == ("uri", "query"):
                # The uri query span keeps its leading '?' (rendered '&'
                # by the normalization); as QueryStringFieldDissector
                # input that first separator only produces an empty
                # segment the host skips — start the split past it.
                first = extract(b32, s, 1)[:, 0]
                s = jnp.where(
                    (s < e) & (first == np.uint8(ord("?"))), s + 1, s
                )
            csr = postproc.split_csr(
                b32, s, e, layout.csr_slots,
                sep=_CSR_SEPARATORS[plan.meta or "query"],
                # URI-chained query strings pass through the URI encode
                # step before the host dissector sees them — encode-set
                # bytes flag the per-row path.  Direct token captures
                # (nginx $args) and cookies are raw header text: no.
                uri_encoded=bool(plan.steps) and plan.steps[-1][0] == "uri",
                window=CSR_WINDOW_PER_SLOT * layout.csr_slots,
                extract=extract,
            )
            if not plan.steps:
                # Direct token capture of the query string: CLF null ->
                # no params delivered.
                chain_ok = chain_ok & ~clf_dash(s, e)
            for k in range(layout.csr_slots):
                seg_s = csr["seg_start"][k]
                seg_e = csr["seg_end"][k]
                eq = csr["eq_pos"][k]
                seg_empty = seg_s >= seg_e
                nlen = jnp.where(seg_empty, 0, eq - seg_s)
                has_eq = (~seg_empty) & (eq < seg_e)
                vstart = jnp.minimum(eq + 1, seg_e)
                vlen = jnp.where(has_eq, seg_e - vstart, 0)
                put(key, f"s{k}_start", jnp.where(seg_empty, 0, seg_s))
                put(key, f"s{k}_nlen", nlen)
                put(key, f"s{k}_eq", jnp.where(has_eq, 1, 0))
                put(key, f"s{k}_dec", jnp.where(csr["decode"][k], 1, 0))
                put(key, f"s{k}_ndec", jnp.where(csr["name_pct"][k], 1, 0))
                put(key, f"s{k}_nhigh", jnp.where(csr["name_high"][k], 1, 0))
                put(key, f"s{k}_vstart", jnp.where(has_eq, vstart, 0))
                put(key, f"s{k}_vlen", vlen)
            put(key, "ok", jnp.where(chain_ok, 1, 0))
            # More segments than slots: the oracle takes the whole line,
            # and the overflow is surfaced in row 0 so the host can react
            # by growing the slot count (adaptive CSR).  Masked by the
            # running line validity so overflow on an already-rejected
            # line cannot trigger permanent slot growth.
            overflowed = csr["overflow"] & chain_ok & valid
            valid = valid & ~overflowed
            csr_overflow_rows.append(overflowed)
        elif plan.kind == "ts":
            if ts_group_key(plan) in group_done:
                continue
            group_done.add(ts_group_key(plan))
            comp, ok = timeparse.parse_device_timestamp(
                b32, s, e, plan.meta, extract
            )
            key = ts_group_key(plan)
            put(key, "c1",
                comp["year"] | (comp["month"] << 14) | (comp["day"] << 18)
                | (comp["hour"] << 23))
            put(key, "c2",
                comp["minute"] | (comp["second"] << 6) | (comp["milli"] << 12))
            put(key, "off", comp["offset_seconds"])
            put(key, "ok", jnp.where(ok, 1, 0))
            # A timestamp the host layout rejects raises DissectionFailure
            # there, failing the whole line — mirror that: route the line
            # to the oracle (which will reject it identically).
            valid = valid & (ok | ~chain_ok)
        else:  # pragma: no cover
            raise AssertionError(plan.kind)

    for constraint in line_constraints:
        valid = valid & constraint
    row0 = jnp.where(valid, 1, 0).astype(jnp.int32)
    if plausible is not None:
        row0 = row0 | (jnp.where(plausible, 2, 0).astype(jnp.int32))
    if esc_hit is not None:
        # Escaped-quote decode marker: only meaningful on lines this
        # format still claims after every constraint (the host counts
        # device_escaped_quote_lines_total from the winning unit's bit).
        row0 = row0 | jnp.where(
            esc_hit & valid, ESC_QUOTE_BIT, 0
        ).astype(jnp.int32)
    for overflowed in csr_overflow_rows:
        row0 = row0 | jnp.where(overflowed, CSR_OVERFLOW_BIT, 0).astype(
            jnp.int32
        )
    rows[0] = row0
    zero = jnp.zeros(B, dtype=jnp.int32)
    return [r if r is not None else zero for r in rows], view_prefix


# ---------------------------------------------------------------------------
# Entry points: the jnp executor of the packed pipeline.
#
# Multi-format (SURVEY §7.7): the reference keeps ONE active format and
# switches on DissectionFailure (HttpdLogFormatDissector.java:174-204) — a
# stateful, path-dependent scheme.  The vectorized equivalent runs EVERY
# registered format's split automaton over the batch in the same fused
# computation and picks the per-line winner by registration priority
# (deterministic, order-independent — strictly better than active/fallback).
# Each format is one FormatUnit; its rows are stacked into one [sum K_i, B]
# packed output, so multi-format still costs exactly one device->host fetch.
# ---------------------------------------------------------------------------


@dataclass
class FormatUnit:
    """One registered LogFormat's compiled device pipeline: split program +
    per-field plans + packed row layout.  row_offset is its first row in the
    stacked multi-format output (row row_offset = this format's validity)."""

    program: DeviceProgram
    plans: List[FieldPlan]
    layout: PackedLayout
    row_offset: int = 0
    # True for an uncompilable format's separator-order probe
    # (compile_plausibility_program): its single row carries ONLY the
    # plausibility bit — the valid bit stays 0, so it can never claim a
    # line, only contest later formats' claims.
    plausibility_only: bool = False

    @property
    def splits_uri(self) -> bool:
        """Whether :func:`compute_rows` runs the URI split for this unit:
        some device plan chains a ``uri`` step."""
        return any(
            p.kind != "host" and any(name == "uri" for name, _ in p.steps)
            for p in self.plans
        )

    def plan_for(self, field_id: str) -> FieldPlan:
        for p in self.plans:
            if p.field_id == field_id:
                return p
        return FieldPlan(field_id, "host")


def assign_row_offsets(units: Sequence[FormatUnit]) -> int:
    """Set each unit's row_offset; returns the stacked row count K."""
    off = 0
    for u in units:
        u.row_offset = off
        off += u.layout.n_rows
    return off


def packed_row_count(units: Sequence[FormatUnit]) -> int:
    """Stacked packed-output rows of one executor pass over ``units``
    (``assign_row_offsets``'s return value without mutating offsets) —
    the single home of the D2H footprint arithmetic the device byte
    budget reads."""
    return sum(u.layout.n_rows for u in units)


def estimate_device_bytes(
    units: Sequence[FormatUnit],
    n_view_fields: int,
    padded_b: int,
    line_len: int,
    lengths_itemsize: int = 4,
    aggregate_group_ops: Optional[int] = None,
) -> int:
    """Pre-allocation device-footprint estimate for one padded batch:
    the staged H2D input (``[padded_b, line_len]`` uint8 buffer + the
    lengths vector) plus the packed int32 verdict output (one row per
    output component, 4 trailing rows per device-view span field) —
    deliberately the same arithmetic the executor's buffers resolve to,
    so a budget validated against this estimate is a budget the device
    actually sees (docs/FAULTS.md; the batch-tier twin of the serving
    tier's frame ceilings validated before allocation).

    ``aggregate_group_ops`` switches to the analytics-pushdown footprint
    (docs/ANALYTICS.md): the reduction emits no device-view rows and no
    packed-column D2H — its resident peak is the units rows (the parse
    intermediates, before XLA prunes the unread ones) plus the sort
    workspace of the grouping ops (five int32 key/operand lanes each,
    double-buffered by ``lax.sort``).  Without this split, the budget
    charged aggregate batches the full view-emitting row-path footprint
    and over-rejected batches that fit comfortably."""
    rows = packed_row_count(units)
    if aggregate_group_ops is None:
        rows += 4 * int(n_view_fields)
    else:
        rows += 10 * int(aggregate_group_ops)
    input_bytes = padded_b * line_len + padded_b * lengths_itemsize
    return int(input_bytes + rows * padded_b * 4)


def _units_rows_and_prefixes(
    units: Sequence[FormatUnit],
    buf: jnp.ndarray,
    lengths: jnp.ndarray,
    view_specs: Sequence[Tuple[str, Sequence[int]]] = (),
) -> Tuple[List[jnp.ndarray], Dict[Tuple[int, str], Tuple[jnp.ndarray, ...]]]:
    """All formats' packed rows for one batch, plus — when ``view_specs``
    names (field, unit) pairs — each unit's in-pass first-12-byte view
    prefix words, keyed (unit_index, field_id)."""
    rows: List[jnp.ndarray] = []
    prefixes: Dict[Tuple[int, str], Tuple[jnp.ndarray, ...]] = {}
    for ui, u in enumerate(units):
        # Plausibility is computed for EVERY unit (not just non-final
        # ones): besides the multi-format winner contest, the host uses
        # "implausible for all formats" as a sound definitely-bad filter —
        # regex-accept implies plausible, so such lines skip the per-line
        # oracle re-parse entirely.
        if u.plausibility_only:
            # Uncompilable format: one row, plausible bit only (bit 1);
            # the valid bit is never set so the probe cannot win a line.
            _, _, _, plausible, _ = compute_split(
                u.program, buf, lengths, need_plausible=True
            )
            rows.append(jnp.where(plausible, 2, 0).astype(jnp.int32))
            continue
        vf = [fid for fid, unit_idx in view_specs if ui in unit_idx]
        unit_rows, unit_prefix = compute_rows(
            u.program, u.plans, u.layout, buf, lengths,
            need_plausible=True, view_fields=vf,
        )
        rows.extend(unit_rows)
        for fid, words in unit_prefix.items():
            prefixes[(ui, fid)] = words
    return rows, prefixes


def compute_units_rows(
    units: Sequence[FormatUnit],
    buf: jnp.ndarray,
    lengths: jnp.ndarray,
) -> List[jnp.ndarray]:
    """All formats' packed rows for one batch — the single executor body
    shared by the jnp path (via :func:`units_fn`), the mesh runners, and
    bench.py.  Every compare and range check is correct under both uint8
    and int32 inputs: uint8 wraparound "negatives" land >= 230 and int32
    gives true negatives, and each fails the <= 9 / < 26 digit and letter
    range checks identically (the timestamp parser digit-checks every
    numeric byte explicitly for exactly this reason)."""
    rows, _ = _units_rows_and_prefixes(units, buf, lengths)
    return rows


def units_fn(units: Sequence[FormatUnit]):
    """The un-jitted plain-XLA executor body over all formats:
    (buf [B,L] uint8, lengths [B]) -> [sum K_i, B] int32.  The single
    source for build_units_jnp_fn and the sharded mesh runners."""

    def fn(buf: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        # buf stays uint8 end-to-end here: the [B, L] passes are HBM-bound
        # and every compare works on uint8 directly — an int32 up-cast
        # would 4x the traffic.
        return jnp.stack(compute_units_rows(units, buf, lengths))

    return fn


# Device-emitted Arrow view ingredients: 4 extra int32 rows per span field
# appended to the packed output.  Row 0 is the winner-merged span word
# (start | len<<13 | live<<26); rows 1-3 carry the span's first 12 bytes
# (LE-packed, masked beyond len).  The host turns these into Arrow
# string_view structs with one streaming interleave pass
# (native lp_views_interleave) instead of re-streaming the whole [B, L]
# buffer — on the 1-core bench host the byte gather runs at ~6.7 GB/s,
# on the TPU at HBM speed.  The prefix bytes themselves are extracted
# inside each unit's pass (span_prefix_words) and only winner-SELECTED
# here, so view emission adds [B]-shaped selects, not buffer sweeps.
VIEW_ROWS_PER_FIELD = 4
VIEW_LEN_SHIFT = _SPAN_BITS
VIEW_LIVE_SHIFT = 2 * _SPAN_BITS


def compute_view_rows(
    units: Sequence[FormatUnit],
    rows: List[jnp.ndarray],
    view_specs: Sequence[Tuple[str, Sequence[int]]],
    prefixes: Dict[Tuple[int, str], Tuple[jnp.ndarray, ...]],
) -> List[jnp.ndarray]:
    """Winner-merged Arrow view rows for span fields, computed ON DEVICE.

    ``rows`` is the flat list of all units' packed rows (pre-stack);
    ``view_specs`` is [(field_id, [unit_index, ...])] listing, per span
    field, the units the host would decode it from (``_unit_decodable``
    semantics — lines won by other units deliver via oracle overrides and
    the host patches their views).  ``prefixes`` carries each unit's
    in-pass first-12-byte words ((unit_index, field_id) ->
    span_prefix_words output); the merge is pure per-line selects.  The
    winner/contested computation mirrors TpuBatchParser._fetch_packed
    exactly."""
    B = rows[0].shape[0]

    # Per-line winner by registration priority + the contested rule (an
    # earlier format still plausible un-claims the line; the host then
    # routes it to the oracle).
    row0 = [rows[u.row_offset] for u in units]
    validity = jnp.stack([(r & 1) for r in row0])          # [U, B]
    plausible = jnp.stack([((r >> 1) & 1) for r in row0])  # [U, B]
    valid_any = jnp.any(validity != 0, axis=0)
    winner = jnp.argmax(validity, axis=0)
    if len(units) > 1:
        earlier_plausible = jnp.cumsum(plausible, axis=0) - plausible
        # Select-chain instead of take_along_axis: a [U, B] gather lowers
        # to scalar-slow TPU gather ops (+0.18 ms on the 2-unit
        # multiformat config); U is the registered-format count, so U
        # selects are effectively free.
        ep_at_winner = earlier_plausible[0]
        for ui in range(1, len(units)):
            ep_at_winner = jnp.where(
                winner == ui, earlier_plausible[ui], ep_at_winner
            )
        valid_any = valid_any & (ep_at_winner == 0)

    out: List[jnp.ndarray] = []
    zero32 = jnp.zeros(B, dtype=jnp.int32)
    for fid, unit_idx in view_specs:
        merged = zero32
        pwords = [zero32, zero32, zero32]
        for ui in unit_idx:
            u = units[ui]
            r, _, _ = u.layout.slots[fid]["start"]
            w = rows[u.row_offset + r]
            ok = ((w >> (2 * _SPAN_BITS)) & 1) != 0
            null = ((w >> (2 * _SPAN_BITS + 1)) & 1) != 0
            sel = (winner == ui) & valid_any & ok & ~null
            live_word = (w & ((1 << (2 * _SPAN_BITS)) - 1)) | (
                1 << VIEW_LIVE_SHIFT
            )
            merged = jnp.where(sel, live_word, merged)
            unit_words = prefixes[(ui, fid)]
            pwords = [
                jnp.where(sel, unit_words[k], pwords[k]) for k in range(3)
            ]
        out.append(merged)
        out.extend(pwords)
    return out


def units_views_fn(
    units: Sequence[FormatUnit],
    view_specs: Sequence[Tuple[str, Sequence[int]]],
):
    """Executor body emitting packed rows PLUS device view rows:
    [sum K_i + 4 * n_view_fields, B] int32."""

    def fn(buf: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        rows, prefixes = _units_rows_and_prefixes(
            units, buf, lengths, view_specs
        )
        rows.extend(compute_view_rows(units, rows, view_specs, prefixes))
        return jnp.stack(rows)

    return fn


# Tile size for large batches: at 64k x 384 the executor's [B]-shaped
# intermediates overflow fast memory and XLA inserts HBM<->S(1) copies
# that dominate the profile (39.6M lines/s @64k vs 47.2M @16k for the
# same program).  lax.map over 16k tiles keeps each tile's working set
# resident; the per-tile outputs re-pack into the same [K, B] layout.
EXEC_TILE_B = 16384


def build_units_jnp_fn(
    units: Sequence[FormatUnit],
    view_specs: Optional[Sequence[Tuple[str, Sequence[int]]]] = None,
    mesh=None,
):
    """Plain-XLA executor over all formats:
    (buf [B,L] uint8, lengths [B]) -> [sum K_i, B] int32 (plus 4 trailing
    device-view rows per span field when ``view_specs`` is given).

    ``mesh`` (a ``jax.sharding.Mesh`` with a ``data`` axis) lays the
    batch dimension out data-parallel over the mesh's devices via
    ``NamedSharding``/``PartitionSpec`` — the dryrun_multichip /
    batch_parallel_runner machinery promoted to the product hot path.
    The per-line computation has no cross-line dependency, so XLA
    partitions it with zero collectives; output stays the packed
    ``[K, B]`` with the batch column axis sharded, bit-identical to the
    single-device executor (tests/test_parallel.py).  The compile-memory
    tiling below is skipped under a mesh: each device already sees only
    ``B / n_data`` rows, and reshaping a sharded batch axis into tiles
    would force cross-device resharding."""
    fn = (
        units_views_fn(units, view_specs) if view_specs
        else units_fn(units)
    )

    if mesh is not None:
        from ..parallel.mesh import dp_shardings

        in_shardings, out_shardings = dp_shardings(mesh)
        return jax.jit(
            fn, in_shardings=in_shardings, out_shardings=out_shardings
        )

    def tiled(buf: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
        B = buf.shape[0]
        if B > EXEC_TILE_B and B % EXEC_TILE_B == 0:
            n = B // EXEC_TILE_B
            tb = buf.reshape(n, EXEC_TILE_B, buf.shape[1])
            tl = lengths.reshape(n, EXEC_TILE_B)
            # Shape probe (traced once, free): rows K + dtype of the
            # packed output for the result allocation.
            probe = jax.eval_shape(fn, tb[0], tl[0])
            K = probe.shape[0]

            def body(i, acc):
                # Write each tile's [K, TILE] block straight into the
                # [K, B] result — no [n, K, TILE] intermediate and no
                # final transpose pass (lax.map needed both).
                tile = fn(
                    jax.lax.dynamic_index_in_dim(tb, i, keepdims=False),
                    jax.lax.dynamic_index_in_dim(tl, i, keepdims=False),
                )
                return jax.lax.dynamic_update_slice(
                    acc, tile, (0, i * EXEC_TILE_B)
                )

            init = jnp.zeros((K, B), dtype=probe.dtype)
            return jax.lax.fori_loop(0, n, body, init)
        return fn(buf, lengths)

    return jax.jit(tiled)
