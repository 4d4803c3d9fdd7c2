"""Device kernel profile for a compiled parser: where the milliseconds go.

``jax.profiler.trace`` captures the chip this process owns, and the
xplane protobuf is parseable with the in-image tensorflow (
``tensorflow.tsl.profiler.protobuf.xplane_pb2``), so this tool runs the
fused executor under the profiler and prints per-fusion device time —
ground truth the marginal-slope estimator in bench.py cannot give
(it is jitter- and floor-limited; see ROADMAP).

Set LOGPARSER_TPU_XPROF_STAGES=1 (or call
``logparser_tpu.enable_stage_annotations()``) before capturing and the
host planes of the same xplane trace carry ``lp.<stage>`` scopes named
exactly like the metrics registry's pipeline stages
(docs/OBSERVABILITY.md) — device fusions and host stages line up in one
timeline.

Usage::

    python -m logparser_tpu.tools.profile_device            # headline parser
    python -m logparser_tpu.tools.profile_device --batch 65536 --iters 10
"""
from __future__ import annotations

import glob
import os
import tempfile
from typing import Dict, List, Optional, Tuple

from .demolog import HEADLINE_FIELDS


def profile_parser(
    parser, lines, iters: int = 5, views: bool = False
) -> Optional[List[Tuple[str, float]]]:
    """Run the parser's fused executor under jax.profiler and return
    [(event name, total_ms)] for the device plane, descending; None when
    the xplane proto module is unavailable.  ``views=True`` profiles the
    parse_batch product path (device-emitted Arrow view rows included)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ..tpu.runtime import encode_batch

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception:
        return None

    buf, lengths, _ = encode_batch(lines)
    fn = parser.device_views_fn() if views else parser.device_fn()
    if fn is None:
        return []
    jb, jl = jnp.asarray(buf), jnp.asarray(lengths)
    np.asarray(fn(jb, jl))  # compile + warm
    import shutil

    out_dir = tempfile.mkdtemp(prefix="lpprof")
    try:
        with jax.profiler.trace(out_dir):
            for _ in range(iters):
                np.asarray(fn(jb, jl))

        totals: Dict[str, int] = {}
        for path in glob.glob(
            os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True
        ):
            xs = xplane_pb2.XSpace()
            with open(path, "rb") as f:
                xs.ParseFromString(f.read())
            for plane in xs.planes:
                if (
                    "TPU" not in plane.name
                    and "device" not in plane.name.lower()
                ):
                    continue
                for line in plane.lines:
                    for ev in line.events:
                        name = plane.event_metadata[ev.metadata_id].name
                        totals[name] = totals.get(name, 0) + ev.duration_ps
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return sorted(
        ((name, ps / 1e9) for name, ps in totals.items()),
        key=lambda kv: -kv[1],
    )


def main() -> None:  # pragma: no cover - CLI
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--format", default="combined")
    ap.add_argument("--fields", nargs="*", default=None)
    args = ap.parse_args()

    from .demolog import generate_combined_lines
    from ..tpu.batch import TpuBatchParser

    parser = TpuBatchParser(args.format, args.fields or HEADLINE_FIELDS)
    lines = generate_combined_lines(args.batch, seed=42)
    prof = profile_parser(parser, lines, iters=args.iters)
    if prof is None:
        print("xplane proto module unavailable (needs tensorflow)")
        return
    if not prof:
        print("no device events")
        return
    # The largest event is the jit module envelope (it nests the fusions
    # listed below — summing everything would double-count).
    envelope_ms = prof[0][1]
    per_iter = envelope_ms / args.iters
    print(
        f"module envelope {envelope_ms:.2f} ms over {args.iters} iters "
        f"({per_iter:.3f} ms/batch of {args.batch} -> "
        f"{args.batch / per_iter * 1000:,.0f} lines/s kernel-time)"
    )
    for name, ms in prof[: args.top]:
        print(f"  {ms:9.3f} ms  {name[:100]}")


if __name__ == "__main__":  # pragma: no cover
    main()
