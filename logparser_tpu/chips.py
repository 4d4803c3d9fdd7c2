"""One TPU chip per device-owning child process.

A TPU chip belongs to one process at a time.  Parents that start several
device-owning children on one host (the front tier's sidecars, a pod's
local hosts) pin child ``i`` to chip ``i`` through libtpu's per-process
visibility variables, and refuse at construction to start more children
than the host has chips.

This module never imports JAX: a parent that has touched JAX holds the
chip its children need.  Chips are counted the way JAX's own Cloud TPU
bootstrap counts them, from the PCI bus.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

_GOOGLE_PCI_VENDOR = "0x1ae0"
# TPU PCI device ids (v3, v4, v5p, v5e, v6e, 7x), as jax._src.hardware_utils.
_TPU_PCI_DEVICES = {"0x0027", "0x005e", "0x0062", "0x0063", "0x006f",
                    "0x0076"}
# libtpu's default slice-builder port; each pinned child gets its own.
_BASE_PORT = 8476


class ChipOversubscribedError(ValueError):
    """More device-owning children were asked for than the host has
    chips."""


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def _tpu_count() -> int:
    """The TPU chips this process can open: their device nodes
    (``/dev/accel*``, or ``/dev/vfio/<group>`` on v5e/v6e).  A machine
    carved out of a larger host may list more chips on the PCI bus than
    it lets a process open; the bus is the fallback where no node is
    visible."""
    nodes = (glob.glob("/dev/accel[0-9]*")
             or glob.glob("/dev/vfio/[0-9]*"))
    if nodes:
        return len(nodes)
    n = 0
    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        if _read(vendor) != _GOOGLE_PCI_VENDOR:
            continue
        device = _read(os.path.join(os.path.dirname(vendor), "device"))
        if device in _TPU_PCI_DEVICES:
            n += 1
    return n


def host_chips() -> List[int]:
    """The TPU chips this process may hand to its children: none when JAX
    is held off the TPU (``JAX_PLATFORMS`` without ``tpu``) or the host
    has none; the process's own ``TPU_VISIBLE_CHIPS`` when it was pinned
    itself; else every chip on the PCI bus."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return []
    visible = os.environ.get("TPU_VISIBLE_CHIPS", "").strip()
    if visible:
        return [int(c) for c in visible.split(",") if c.strip()]
    return list(range(_tpu_count()))


def chip_env(chip: int) -> Dict[str, str]:
    """Environment that gives a child process exactly one chip: a
    one-process, one-chip slice with its own slice-builder port.  The
    host-wide libtpu lock would refuse every child after the first;
    ``TPU_VISIBLE_CHIPS`` is what keeps them apart instead."""
    port = str(_BASE_PORT + chip)
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": port,
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def assign_chips(n_children: int,
                 chips: Optional[List[int]] = None) -> List[Dict[str, str]]:
    """Per-child environment overrides for ``n_children`` device-owning
    children: child ``i`` gets chip ``chips[i]``.  A lone child, or any
    child on a host without chips, gets ``{}``: it is the only process on
    the chips there are.  Raises :class:`ChipOversubscribedError` when
    there are fewer chips than children, so children are never left to
    fight over chip 0."""
    if chips is None:
        chips = host_chips()
    if not chips or n_children <= 1:
        return [{} for _ in range(n_children)]
    if n_children > len(chips):
        raise ChipOversubscribedError(
            f"{n_children} device-owning processes requested but this host "
            f"has {len(chips)} TPU chip(s) ({chips}); one process per chip"
        )
    return [chip_env(chips[i]) for i in range(n_children)]
