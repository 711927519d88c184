"""Op registry — implementation selection with availability probing.

Capability parity with the reference's ``op_builder/`` registry
(ALL_OPS + per-builder is_compatible() probing, deepspeed/ops/__init__.py):
each logical op registers candidate implementations with a probe and a
priority; ``get_op`` returns the best available (TPU kernel > XLA fallback),
and ``compatibility_report`` feeds ds_report's op table. Probes run lazily
and cache — the reference JIT-builds CUDA where we JIT-compile Pallas/C++.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import logger


@dataclasses.dataclass
class OpImpl:
    name: str                       # e.g. "pallas_flash"
    loader: Callable[[], Any]       # returns the callable op (may raise)
    probe: Callable[[], bool]       # cheap availability check
    priority: int = 0               # higher wins


class OpRegistry:
    def __init__(self):
        self._impls: Dict[str, List[OpImpl]] = {}
        self._probe_cache: Dict[str, bool] = {}

    def register(self, op: str, impl: OpImpl) -> None:
        self._impls.setdefault(op, []).append(impl)
        self._impls[op].sort(key=lambda i: -i.priority)

    def available(self, op: str, impl_name: str) -> bool:
        key = f"{op}/{impl_name}"
        if key not in self._probe_cache:
            impl = self._find(op, impl_name)
            try:
                self._probe_cache[key] = bool(impl.probe())
            except Exception as e:
                # a probe that RAISES is a fault, not an answer: say so
                logger.warning("op probe %s raised (%s: %s); reporting it "
                               "unavailable", key, type(e).__name__, e)
                self._probe_cache[key] = False
        return self._probe_cache[key]

    def _find(self, op: str, impl_name: str) -> OpImpl:
        for impl in self._impls.get(op, []):
            if impl.name == impl_name:
                return impl
        raise KeyError(f"no impl '{impl_name}' for op '{op}'")

    def get_op(self, op: str, impl: Optional[str] = None) -> Any:
        """Best available implementation (or the named one)."""
        if op not in self._impls:
            raise KeyError(f"unknown op '{op}'; have {sorted(self._impls)}")
        candidates = ([self._find(op, impl)] if impl
                      else self._impls[op])
        for c in candidates:
            if self.available(op, c.name):
                return c.loader()
        raise RuntimeError(f"no available implementation for op '{op}' "
                           f"(tried {[c.name for c in candidates]})")

    def compatibility_report(self) -> Dict[str, Dict[str, bool]]:
        return {op: {i.name: self.available(op, i.name) for i in impls}
                for op, impls in sorted(self._impls.items())}


REGISTRY = OpRegistry()


def _on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def _register_builtins():
    def _flash():
        from .pallas.flash_attention import flash_attention
        return flash_attention

    def _ref_attn():
        from .attention import mha_reference
        return mha_reference

    def _bs_flash():
        from .pallas.block_sparse_attention import block_sparse_flash_attention
        return block_sparse_flash_attention

    def _cpu_adam():
        from .cpu.adam import DeepSpeedCPUAdam
        return DeepSpeedCPUAdam

    def _cpu_adam_numpy():
        import functools

        from .cpu.adam import DeepSpeedCPUAdam
        return functools.partial(DeepSpeedCPUAdam, use_native=False)

    def _aio():
        from .cpu.aio import AsyncIOHandle
        return AsyncIOHandle

    def _aio_python():
        import functools

        from .cpu.aio import AsyncIOHandle
        return functools.partial(AsyncIOHandle, use_native=False)

    REGISTRY.register("attention", OpImpl(
        "pallas_flash", _flash, _on_tpu, priority=10))
    REGISTRY.register("attention", OpImpl(
        "xla_reference", _ref_attn, lambda: True, priority=0))
    REGISTRY.register("sparse_attention", OpImpl(
        "pallas_block_sparse", _bs_flash, _on_tpu, priority=10))
    REGISTRY.register("cpu_adam", OpImpl(
        "cpp_simd", _cpu_adam,
        lambda: __import__("deepspeed_tpu.ops.cpu.build",
                           fromlist=["load_cpu_kernels"]
                           ).load_cpu_kernels() is not None, priority=10))
    REGISTRY.register("cpu_adam", OpImpl(
        "numpy", _cpu_adam_numpy, lambda: True, priority=0))
    REGISTRY.register("aio", OpImpl(
        "cpp_threadpool", _aio,
        lambda: __import__("deepspeed_tpu.ops.cpu.build",
                           fromlist=["load_aio"]).load_aio() is not None,
        priority=10))
    REGISTRY.register("aio", OpImpl("python", _aio_python, lambda: True,
                                    priority=0))

    def _native_loader():
        from ..runtime.data_pipeline.native_loader import NativeBatchAssembler
        return NativeBatchAssembler

    def _py_loader():
        import functools

        from ..runtime.data_pipeline.native_loader import NativeBatchAssembler
        return functools.partial(NativeBatchAssembler, use_native=False)

    REGISTRY.register("data_loader", OpImpl(
        "cpp_mmap", _native_loader,
        lambda: __import__("deepspeed_tpu.ops.cpu.build",
                           fromlist=["load_data_loader"]
                           ).load_data_loader() is not None, priority=10))
    REGISTRY.register("data_loader", OpImpl("python", _py_loader,
                                            lambda: True, priority=0))


_register_builtins()


def get_op(op: str, impl: Optional[str] = None) -> Any:
    return REGISTRY.get_op(op, impl)


def compatibility_report() -> Dict[str, Dict[str, bool]]:
    return REGISTRY.compatibility_report()
