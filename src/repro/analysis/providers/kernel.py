"""InstrumentedKernelProvider: the measured counter path.

Launches the instrumented Pallas kernel described by the spec and reads
the in-kernel ``wave_degrees`` counters back (via the kernel families'
``collect_counters()`` hooks) — nothing is synthesized on the host.
This is the paper's "measured" column.  On a TPU the kernel is compiled
by Mosaic and runs on the chip; on any other backend it runs in the
Pallas interpreter (the CPU test path) and counts the same stream.
Every ``CounterSet`` records which it was (``meta``: ``platform``,
``device_kind``, ``interpret``), and so does every persistent cache key
(``origin``).  On a TPU the attached chip must be the one the session
models: a chip of another registered kind, or of a kind the registry
does not know, raises — no rates are assumed for it.

``indices`` sources are routed through the instrumented scatter-add
kernel (the index stream becomes a unit-value scatter), so even synthetic
streams can be cross-validated against in-kernel counters.
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
        if spec.kernel is not None:
            # spec.run_kernel() owns the op dispatch and geometry
            # threading (one definition, shared with resolve_trace); the
            # per-family ops also expose collect_counters() hooks for
            # direct low-level use outside a Session.
            tr, meta = spec.run_kernel(), {"op": spec.kernel.op}
        elif spec.indices is not None:
            return self._collect_indices(spec)
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

    def _collect_indices(self, spec) -> CounterSet:
        """Run a bare index stream through the instrumented scatter-add.

        Geometry defaults mirror ``trace_from_indices`` (waves_per_tile 1)
        so the 'trace' and 'kernel' providers agree bit-for-bit.  The
        stream length must be a multiple of the kernel tile: a shorter
        stream would be sentinel-padded by the launch, and the padding
        waves would be *counted* — the measured N/e would then silently
        diverge from the trace provider's (which models the raw stream),
        turning every ``validate()`` into a false alarm.  Refuse instead.
        """
        import numpy as np

        from repro.kernels.scatter_add import ops as scat_ops  # lazy: jax

        idx = np.asarray(spec.indices).reshape(-1)
        tile = scat_ops.sk.DEFAULT_TILE
        if idx.size % tile != 0:
            raise ValueError(
                f"WorkloadSpec {spec.label!r}: the 'kernel' provider needs "
                f"an index stream sized to a multiple of the scatter tile "
                f"({tile}); got {idx.size}. Pad the stream, or use "
                f"WorkloadSpec.from_scatter_add (both providers then share "
                f"the kernel's own sentinel padding).")
        return scat_ops.collect_counters(
            idx, np.ones(idx.shape, np.float32), spec.num_bins,
            label=spec.label, num_cores=spec.num_cores,
            job_class=spec.job_class,
            waves_per_tile=spec.waves_per_tile or 1,
            pipeline_depth=spec.pipeline_depth or 2,
            bytes_read=spec.bytes_read, flops=spec.flops,
            overhead_cycles=spec.overhead_cycles)


register_provider(InstrumentedKernelProvider())
