"""paddle.dataset.common parity (dataset/common.py): md5 + cache-dir
helpers (download() itself needs network and raises with guidance)."""
from __future__ import annotations

import hashlib
import os

from .. import _paths

__all__ = ["DATA_HOME", "md5file", "download"]

DATA_HOME = _paths.cache_path("dataset")


def md5file(fname: str) -> str:
    h = hashlib.md5()
    with open(fname, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def download(url, module_name, md5sum, save_name=None):
    path = os.path.join(DATA_HOME, module_name,
                        save_name or url.split("/")[-1])
    if os.path.exists(path) and (not md5sum or md5file(path) == md5sum):
        return path
    raise RuntimeError(
        f"dataset download needs network access, unavailable in this "
        f"build; place the file at {path!r} (md5 {md5sum}) and retry")
