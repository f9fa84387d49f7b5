"""TPU device plane: policy compilation + batched evaluation.

The scalar engine (kyverno_tpu.engine) is the semantic oracle; this
package compiles a policy set into a trace-time-specialized JAX
program that evaluates the policy x resource cross-product as one
batched device computation:

- flatten:   resource JSON -> padded row tables (path hashes, typed
             value lanes pre-parsed on host, byte pool for globs)
- metadata:  match/exclude features (GVK, name/ns bytes, label hashes)
- ir:        Rule -> device IR with capability analysis; rules using
             constructs outside the device subset fall back to the
             scalar engine per rule (never wrong, only slower)
- evaluator: IR -> jitted batch program, vmapped over resources and
             unrolled over rules; MXU-friendly instance joins
- engine:    TpuEngine facade + sharded scan entry points
- cache:     content-addressed verdict/encode LRUs + the persistent
             XLA compile cache (the amortization levers)
- pipeline:  double-buffered scan — encode k+1, device k, and host
             completion k-1 overlap instead of serializing
"""

# Lazy exports (PEP 562): the compiler/engine pull in JAX, but the
# encode-pool worker processes (encode/worker.py) import ONLY the host
# side of this package (flatten, metadata, hashing) and must stay
# JAX-free — an eager import here would load the full device runtime
# into every spawned encoder.
_LAZY = {
    "CompiledPolicySet": ".compiler",
    "compile_policy_set": ".compiler",
    "ScanResult": ".engine",
    "TpuEngine": ".engine",
}


_device = None


def device_info():
    """What the engine dispatches to: JAX's default platform, its
    device_kind, the device count, and the seconds the first call took
    to attach them (serve logs it at start; /debug/state shows it)."""
    global _device
    if _device is None:
        import time

        import jax

        t0 = time.perf_counter()
        devices = jax.devices()
        _device = {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "attach_s": time.perf_counter() - t0}
    return _device


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod, __name__), name)


def __dir__():
    return sorted(list(globals()) + list(_LAZY))

