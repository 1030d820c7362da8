"""InstrumentedKernelProvider: the measured counter path.

Launches the instrumented Pallas kernel described by the spec
(``WorkloadSpec.run_kernel``) and reads the in-kernel ``wave_degrees``
counters back — nothing is synthesized on the host.
This is the paper's "measured" column.  On a TPU the kernel is compiled
by Mosaic and runs on the chip; on any other backend it runs in the
Pallas interpreter (the CPU test path) and counts the same stream.
Every ``CounterSet`` records which it was (``meta``: ``platform``,
``device_kind``, ``interpret``), and so does every persistent cache key
(``origin``).  On a TPU the attached chip must be the one the session
models: a chip of another registered kind, or of a kind the registry
does not know, raises — no rates are assumed for it.

``indices`` sources run through the instrumented scatter-add kernel (the
index stream becomes a unit-value scatter), so even synthetic streams can
be cross-validated against in-kernel counters.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

from repro.analysis.device import DEVICES
from repro.analysis.providers.base import (collect_batch_fallback,
                                           register_provider)
from repro.core.counters import CounterFrame, CounterSet
from repro.obs import telemetry


def _backend(device) -> dict:
    """The attached backend, checked against the modeled ``device``."""
    from repro import kernels  # lazy: jax

    info = kernels.backend_info()
    if info["platform"] != "tpu":
        return info
    kind = info["device_kind"]
    if not any(kind in d.device_kinds for d in DEVICES.values()):
        raise RuntimeError(
            f"attached TPU reports device kind {kind!r}, which no "
            f"registered Device models; register one for it "
            f"(repro.analysis.register_device) before collecting")
    if kind not in device.device_kinds:
        raise RuntimeError(
            f"session models {device.name!r} "
            f"({', '.join(device.device_kinds) or 'no device kind'}) but "
            f"the attached TPU is {kind!r}")
    return info


class InstrumentedKernelProvider:
    """Counters read back from an instrumented Pallas launch."""

    name = "kernel"

    def origin(self) -> str:
        from repro import kernels  # lazy: jax

        info = kernels.backend_info()
        return (f"{info['platform']}|{info['device_kind']}|"
                f"interpret={info['interpret']}")

    def collect_batch(self, specs: Sequence, device, *,
                      parallel: Optional[int] = None) -> CounterFrame:
        """Grouped loop fallback: kernel launches have no batched form
        (each spec is its own Pallas launch), so the batch is one scalar
        ``collect`` per spec — still one provider call per sweep group
        from the Session's point of view."""
        return collect_batch_fallback(self, specs, device, parallel)

    def collect(self, spec, device) -> CounterSet:
        info = _backend(device)
        cset = self._collect(spec)
        return dataclasses.replace(cset, meta={**cset.meta, **info})

    def _collect(self, spec) -> CounterSet:
        if spec.kernel is not None or spec.indices is not None:
            # spec.run_kernel() owns the op dispatch and geometry
            # threading (one definition, shared with resolve_trace)
            op = "scatter_add" if spec.kernel is None else spec.kernel.op
            tr, meta = spec.run_kernel(), {"op": op}
        elif spec.run is not None:
            # custom lazy source: by contract it runs an instrumented
            # kernel and returns its trace
            tr, meta = spec.resolve_trace(), None
        else:
            raise ValueError(
                f"WorkloadSpec {spec.label!r} has no runnable source — the "
                f"'kernel' provider needs a kernel | indices | run spec, "
                f"not a pre-recorded trace or compiled artifact")
        with telemetry.span("kernel.counters"):
            return CounterSet.from_trace(
                tr, label=spec.label, num_cores=spec.num_cores,
                bytes_read=spec.bytes_read, flops=spec.flops,
                overhead_cycles=spec.overhead_cycles, source=self.name,
                meta=meta)


register_provider(InstrumentedKernelProvider())
