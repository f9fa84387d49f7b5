"""Native (C) components, compiled lazily with the system toolchain.

The runtime around the XLA compute path is allowed to be native; the
resource encoder is the scan pipeline's serial host bottleneck, so its
hot walk lives in fastencode.c (see that file's header for the parity
contract with the Python oracle). The built extension is named by a
hash of the source, the compiler command and the Python ABI, so a
stale or copied binary never loads: a changed source builds a new
file. A failed build, or KYVERNO_TPU_NATIVE=0, degrades to the Python
encoder — correctness never depends on the toolchain — and says so on
the op log and in status()."""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
from typing import Any, Dict, Optional

_FLAGS = ("-O2", "-shared", "-fPIC")

_mod = None
_tried = False
_so: Optional[str] = None
_error: Optional[str] = None


def _build_key(src: str, cc: str, inc: str) -> str:
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update(repr((cc, _FLAGS, inc, sys.implementation.cache_tag,
                   sysconfig.get_config_var("EXT_SUFFIX"))).encode())
    return h.hexdigest()[:16]


def load() -> Optional[object]:
    """Build (once per source/flags hash) and import _fastencode."""
    global _mod, _tried, _so, _error
    if _tried:
        return _mod
    _tried = True
    if os.environ.get("KYVERNO_TPU_NATIVE", "1") == "0":
        _error = "disabled by KYVERNO_TPU_NATIVE=0"
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "fastencode.c")
    try:
        inc = sysconfig.get_paths()["include"]
        cc = os.environ.get("CC", "gcc")
        so = os.path.join(here, f"_fastencode-{_build_key(src, cc, inc)}.so")
        if not os.path.exists(so):
            tmp = so + f".tmp{os.getpid()}"
            subprocess.run([cc, *_FLAGS, f"-I{inc}", src, "-o", tmp],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)  # atomic: concurrent builders race safely
        spec = importlib.util.spec_from_file_location("_fastencode", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        _mod, _so = mod, so
    except Exception as e:
        stderr = getattr(e, "stderr", None)
        _error = f"{type(e).__name__}: {e}" + (
            f": {stderr.decode(errors='replace')[-500:]}" if stderr else "")
        try:
            from ..observability.log import global_oplog

            global_oplog.emit("native_encoder_unavailable", level="warn",
                              error=_error)
        except Exception:
            pass
    return _mod


def status() -> Dict[str, Any]:
    """Whether the native encoder loaded (None: not tried yet), from
    which file, and why not."""
    return {"loaded": (_mod is not None) if _tried else None,
            "so": _so, "error": _error}
