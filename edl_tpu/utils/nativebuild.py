"""Where the native components (``native/coordinator``,
``native/scheduler``) are built and loaded from — one rule for both
bindings.

Default: ``native/<component>/build`` inside the checkout (git-ignored,
rebuilt when a source is newer). ``EDL_NATIVE_BUILD_DIR=<dir>`` is the
deployment path that moves it to ``<dir>/<component>``: a read-only
tree builds elsewhere, and ``chip_smoke.py`` points it at a clean
directory so a stale binary that came along with a copied tree cannot
be the one that runs.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def source_dir(component: str) -> str:
    return os.path.join(_REPO_ROOT, "native", component)


def build_dir(component: str) -> str:
    moved = os.environ.get("EDL_NATIVE_BUILD_DIR")
    if moved:
        return os.path.join(moved, component)
    return os.path.join(source_dir(component), "build")
