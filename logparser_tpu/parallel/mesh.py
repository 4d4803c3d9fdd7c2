"""Distributed execution: device meshes, data-parallel and sequence-parallel
split-program execution.

The reference's "distributed layer" is embarrassingly-parallel data parallelism
over line batches: the host engine splits the file and ships a serialized
parser config to independent workers (SURVEY §2.4/§5.8).  The TPU-native
equivalent:

- **DP**: shard the batch dimension of the ``[B, L]`` buffer over a
  ``jax.sharding.Mesh`` axis; the split program has no cross-line dependency,
  so XLA partitions it with zero collectives in the hot loop.  Counter
  aggregation (good/bad lines) is the only cross-device reduction.
- **SP (long lines)**: the analogous axis to "long context" is line length
  (SURVEY §5.7).  ``run_program_sp`` shards L over a ``seq`` mesh axis inside
  ``shard_map``: every find-literal op computes a local candidate position and
  resolves the global first occurrence with ``lax.pmin`` over the seq axis;
  multi-byte separators crossing shard boundaries are handled with a halo
  exchange via ``lax.ppermute``; charset validation aggregates violation
  counts with ``lax.psum``.  Collectives ride ICI; no host round-trips.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..tpu.program import DeviceProgram
from ..tpu.runtime import _run_program_impl


def make_mesh(
    n_data: int, n_seq: int = 1, devices: Optional[Sequence] = None
) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = n_data * n_seq
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(n_data, n_seq)
    return Mesh(dev_array, axis_names=("data", "seq"))


def dp_device_count(requested: Optional[int] = None) -> int:
    """The data-parallel width a parser mesh should use: the largest
    power of two <= min(requested, local device count).  Power-of-two
    widths always divide the power-of-two batch buckets the parser pads
    to, so the sharded batch axis never needs uneven-shard handling in
    the hot path; a leftover odd device idles rather than forcing a
    repad (document, don't surprise)."""
    avail = len(jax.devices())
    n = avail if requested is None else min(int(requested), avail)
    if n < 1:
        return 1
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def dp_shardings(mesh: Mesh):
    """The ONE definition of the fused parse step's data-parallel
    layout: inputs ``(buf [B, L], lengths [B])`` sharded over the
    ``data`` axis, packed output ``[K, B]`` sharded on its batch
    column axis.  Shared by :func:`batch_parallel_runner` (the dryrun /
    test harness) and ``TpuBatchParser(data_parallel=...)`` (the
    product hot path) so the two can never drift."""
    return (
        (
            NamedSharding(mesh, P("data", None)),
            NamedSharding(mesh, P("data")),
        ),
        NamedSharding(mesh, P(None, "data")),
    )


# ---------------------------------------------------------------------------
# Data-parallel execution: shard B, replicate the program.
# ---------------------------------------------------------------------------

def data_parallel_runner(program: DeviceProgram, mesh: Mesh):
    """jitted fn(buf [B, L], lengths [B]) with batch sharded over 'data'."""
    in_shardings = (
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data")),
    )
    fn = functools.partial(_run_program_impl, program)
    return jax.jit(fn, in_shardings=in_shardings)


def batch_parallel_runner(units, mesh: Mesh, view_specs=None):
    """The FULL fused field-extraction step under data parallelism:
    jitted fn(buf [B, L], lengths [B]) -> packed [K, B] int32 with the
    batch axis sharded over 'data'.

    Unlike :func:`data_parallel_runner` (split program only), this shards
    the complete per-parser pipeline — split + chained sub-dissector
    stages (firstline/URI splits, timestamps, CSR wildcards, GeoIP joins)
    — exactly what ``TpuBatchParser`` executes per batch.  The per-line
    computation has no cross-line dependency, so XLA partitions it with
    zero collectives in the hot loop.  ``view_specs`` (round 5) appends
    the device-emitted Arrow view rows, sharded the same way — the
    parse_batch product path."""
    from ..tpu.pipeline import units_fn, units_views_fn

    # The same executor body TpuBatchParser jits.
    fn = units_views_fn(units, view_specs) if view_specs else units_fn(units)

    in_shardings, out_shardings = dp_shardings(mesh)
    return jax.jit(fn, in_shardings=in_shardings, out_shardings=out_shardings)


# ---------------------------------------------------------------------------
# Sequence-parallel execution: shard L over 'seq' inside shard_map.
# ---------------------------------------------------------------------------

def _sp_find_literal(buf_local, lengths, lit, cursor, offset, l_total, axis):
    """Global first occurrence >= cursor of `lit`, with halo for multi-byte
    literals; returns l_total when absent."""
    B, Lc = buf_local.shape
    n_lit = len(lit)

    if n_lit > 1:
        n_shards = lax.psum(1, axis)
        perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]
        halo = lax.ppermute(buf_local[:, : n_lit - 1], axis, perm)
        ext = jnp.concatenate([buf_local, halo], axis=1)
    else:
        ext = buf_local

    match = jnp.ones((B, Lc), dtype=bool)
    for k, byte in enumerate(lit):
        match = match & (ext[:, k : k + Lc] == np.uint8(byte))

    local_pos = jnp.arange(Lc, dtype=jnp.int32)
    global_pos = local_pos[None, :] + offset
    usable = (
        match
        & (global_pos + n_lit <= lengths[:, None])
        & (global_pos >= cursor[:, None])
    )
    cand = jnp.where(usable, global_pos, l_total)
    local_min = jnp.min(cand, axis=1)
    return lax.pmin(local_min, axis)


def _sp_byte_at(buf_local, idx, offset, axis):
    """buf[global idx] with each global position owned by one shard."""
    Lc = buf_local.shape[1]
    local = idx - offset
    in_range = (local >= 0) & (local < Lc)
    safe = jnp.clip(local, 0, Lc - 1)
    b = jnp.take_along_axis(buf_local, safe[:, None], axis=1)[:, 0]
    contrib = jnp.where(in_range, b.astype(jnp.int32), 0)
    return lax.psum(contrib, axis)


def _sp_charset_ok(buf_local, start, end, cs_table_row, offset, axis):
    Lc = buf_local.shape[1]
    local_pos = jnp.arange(Lc, dtype=jnp.int32)
    global_pos = local_pos[None, :] + offset
    in_span = (global_pos >= start[:, None]) & (global_pos < end[:, None])
    bad = in_span & ~cs_table_row[buf_local]
    local_bad = jnp.sum(bad.astype(jnp.int32), axis=1)
    return lax.psum(local_bad, axis) == 0


def _sp_program_body(program: DeviceProgram, l_total: int, axis: str,
                     buf_local, lengths):
    B, Lc = buf_local.shape
    offset = lax.axis_index(axis).astype(jnp.int32) * Lc

    cursor = jnp.zeros(B, dtype=jnp.int32)
    valid = jnp.ones(B, dtype=bool)
    n_tok = len(program.tokens)
    starts = jnp.zeros((n_tok, B), dtype=jnp.int32)
    ends = jnp.zeros((n_tok, B), dtype=jnp.int32)
    charset_table = jnp.asarray(program.charset_table)

    for op in program.ops:
        if op.kind == "lit":
            ok = jnp.ones(B, dtype=bool)
            for k, byte in enumerate(op.lit):
                b = _sp_byte_at(buf_local, cursor + k, offset, axis)
                ok = ok & (b == byte)
            ok = ok & (cursor + len(op.lit) <= lengths)
            valid = valid & ok
            cursor = cursor + len(op.lit)
        elif op.kind in ("until_lit", "to_end"):
            if op.kind == "until_lit":
                found = _sp_find_literal(
                    buf_local, lengths, op.lit, cursor, offset, l_total, axis
                )
                token_valid = found < l_total
                start, end = cursor, jnp.where(token_valid, found, cursor)
                valid = valid & token_valid
                next_cursor = end + len(op.lit)
            else:
                start, end = cursor, lengths
                next_cursor = end
            cs_row = charset_table[program.charset_ids[op.charset]]
            valid = (
                valid
                & _sp_charset_ok(buf_local, start, end, cs_row, offset, axis)
                & ((end - start) >= op.min_len)
            )
            if op.max_len:
                valid = valid & ((end - start) <= op.max_len)
            starts = starts.at[op.token_index].set(start)
            ends = ends.at[op.token_index].set(end)
            cursor = next_cursor
        else:  # pragma: no cover
            raise AssertionError(op.kind)

    valid = valid & (cursor == lengths)
    return {"starts": starts, "ends": ends, "valid": valid}


def sequence_parallel_runner(program: DeviceProgram, mesh: Mesh, l_total: int):
    """jitted fn(buf [B, L], lengths [B]) with B sharded over 'data' and L
    sharded over 'seq'; per-op global resolution via pmin/psum collectives."""
    from jax import shard_map

    body = functools.partial(_sp_program_body, program, l_total, "seq")
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P("data", "seq"), P("data")),
        out_specs={"starts": P(None, "data"), "ends": P(None, "data"),
                   "valid": P("data")},
    )
    return jax.jit(mapped)


def aggregate_counters(mesh: Mesh, good: jnp.ndarray, bad: jnp.ndarray):
    """Global good/bad line counters: the only cross-device reduction of the
    DP hot loop (the reference's Hadoop counters, RecordReader.java:118-120)."""

    def reduce_fn(g, b):
        return jnp.sum(g), jnp.sum(b)

    return jax.jit(reduce_fn)(good, bad)
