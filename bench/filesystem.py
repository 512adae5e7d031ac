"""The filesystem a directory lives on, so that the model store is kept
off tmpfs: there its files could never leave the host's memory."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Tuple

# filesystems whose files live in memory
MEMORY_FS = {"tmpfs", "ramfs", "devtmpfs", "overlay-tmpfs"}


def filesystem_of(path: Path) -> Tuple[str, str]:
    """(filesystem type, mount point) of ``path``, from /proc/mounts: the
    longest mount point that contains it."""
    path = os.path.realpath(path)
    best = ("unknown", "/")
    best_len = -1
    with open("/proc/mounts") as f:
        for line in f:
            parts = line.split()
            if len(parts) < 3:
                continue
            mnt = parts[1].replace("\\040", " ")
            inside = path == mnt or path.startswith(mnt.rstrip("/") + "/")
            if inside and len(mnt) > best_len:
                best, best_len = (parts[2], mnt), len(mnt)
    return best


def on_disk(path: Path) -> bool:
    return filesystem_of(path)[0] not in MEMORY_FS
