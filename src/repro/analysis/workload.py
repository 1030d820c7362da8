"""WorkloadSpec: an immutable description of one scatter-heavy launch.

The old call path required the caller to (a) run an instrumented kernel,
(b) mutate ``trace.waves_per_tile`` after the fact, and (c) thread 11
kwargs into ``profiler.profile_scatter_workload``.  A ``WorkloadSpec``
captures all of that declaratively: what runs (an index stream, an
existing wave trace, a described kernel launch, or a compiled artifact),
under which launch geometry, and with which roofline-side inputs (bytes
read, FLOPs, overhead).  Specs are frozen — sweeps derive variants with
``with_()`` instead of mutating shared state.

A spec is deliberately *provider-agnostic*: it describes the workload,
not how its counters are acquired.  ``KernelSource`` keeps the kernel
launch as data (op name + arguments) rather than a baked closure, and the
spec alone knows what each op runs and commits: ``committed_stream()``
synthesizes the committed index stream in numpy (``TraceProvider``, the
heat map) and ``run_kernel()`` launches the instrumented Pallas kernel
(``InstrumentedKernelProvider``) from one and the same spec — the
model-vs-measured split the paper's validation (§5) needs.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import os
import sys
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Optional

import numpy as np

from repro.core import counters as counters_mod
from repro.core import timing
from repro.obs import telemetry as _telemetry

#: arrays of at least this many bytes are hashed as a sha256 tree: fixed
#: chunks hashed on a thread pool (hashlib releases the interpreter lock
#: while it hashes), then the chunk digests in order.  Smaller arrays are
#: hashed flat, the same byte stream as ``arr.tobytes()``.
_TREE_MIN_BYTES = 8 << 20
_TREE_CHUNK_BYTES = 4 << 20
_TREE_TAG = b"sha256-tree/4MiB"
_TREE_MAX_WORKERS = 8

_FINGERPRINT_BYTES = _telemetry.counter(
    "repro_fingerprint_bytes_total",
    "array bytes hashed by WorkloadSpec.fingerprint, by path "
    "(flat: one sha256 over the buffer; chunked: the sha256 tree)",
    ("path",))


_ROUTED_TOKENS = _telemetry.counter(
    "repro_moe_routed_tokens_total",
    "tokens routed on the device by the kernel provider's moe_router "
    "launches, by layer", ("layer",))


def _usable_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:      # no affinity call off Linux
        return os.cpu_count() or 1


def _chunk_digest(chunk: np.ndarray) -> bytes:
    return hashlib.sha256(chunk).digest()


def _hash_array(h, arr: np.ndarray) -> None:
    """Feed a C-contiguous array's bytes to ``h`` in place, never copied.

    The digest depends on the bytes alone, never on the number of
    workers, so keys stay valid across processes and machines.
    """
    data = np.frombuffer(arr, np.uint8)
    if data.nbytes < _TREE_MIN_BYTES:
        _FINGERPRINT_BYTES.inc(data.nbytes, path="flat")
        h.update(data)
        return
    _FINGERPRINT_BYTES.inc(data.nbytes, path="chunked")
    chunks = [data[i:i + _TREE_CHUNK_BYTES]
              for i in range(0, data.nbytes, _TREE_CHUNK_BYTES)]
    workers = min(_usable_cores(), _TREE_MAX_WORKERS, len(chunks))
    if workers > 1:
        with ThreadPoolExecutor(workers) as pool:
            digests = list(pool.map(_chunk_digest, chunks))
    else:
        digests = [_chunk_digest(c) for c in chunks]
    h.update(_TREE_TAG)
    h.update(str(len(chunks)).encode())
    h.update(b"".join(digests))


def _device_arrays(values) -> list:
    """The device-resident (``jax.Array``) members of ``values``."""
    jax = sys.modules.get("jax")    # no jax imported: no device arrays
    if jax is None:
        return []
    return [v for v in values if isinstance(v, jax.Array)]


def _device_digests(arrays: list) -> list:
    """Digests of device-resident arrays, computed on the device; only
    the digests come back (``repro.kernels.digest``)."""
    from repro.kernels import digest  # lazy: jax

    with _telemetry.span("kernel.digest", arrays=len(arrays),
                         bytes=sum(a.nbytes for a in arrays)):
        return digest.digests(arrays)


@dataclasses.dataclass(frozen=True)
class KernelSource:
    """A described (not yet launched) instrumented-kernel source.

    ``op`` names the kernel family (``"histogram"`` | ``"scatter_add"`` |
    ``"moe_router"``); ``params`` holds its source-specific arguments
    (image / ids / values / bins / router).  Launch geometry lives on the
    owning ``WorkloadSpec`` so ``with_()`` derivations apply to the launch
    too.
    """

    op: str
    params: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """One profileable launch: measurement source + geometry + roofline.

    Exactly one of ``trace`` / ``indices`` / ``run`` / ``kernel`` /
    ``compiled``-or-``hlo_text`` is the measurement source (checked at
    construction).  ``run`` is a zero-arg callable returning a
    ``WaveTrace`` — the escape hatch for custom instrumented sources,
    kept lazy so building a sweep's spec list costs nothing until a
    provider collects it.  ``kernel`` is the declarative form the shipped
    providers understand (see ``from_histogram`` / ``from_scatter_add``).
    ``compiled``/``hlo_text`` describe a compiled step for the HLO
    provider (no wave trace; roofline counters only).
    """

    label: str
    # measurement source (one of):
    trace: Optional[counters_mod.WaveTrace] = None
    indices: Optional[np.ndarray] = None
    run: Optional[Any] = None          # () -> WaveTrace, lazy custom source
    kernel: Optional[KernelSource] = None
    compiled: Optional[Any] = None     # jax compiled artifact (HLO provider)
    hlo_text: Optional[str] = None     # post-optimization HLO module text
    # index-stream interpretation (for the ``indices`` source):
    num_bins: int = 256
    job_class: int = timing.FAO
    # launch geometry:
    waves_per_tile: Optional[int] = None   # None: keep the source's own
    pipeline_depth: Optional[int] = None
    num_cores: int = 8
    num_devices: int = 1               # chips (HLO collective accounting)
    # roofline-side inputs:
    bytes_read: float = 0.0
    flops: float = 0.0
    overhead_cycles: float = 500.0

    def __post_init__(self) -> None:
        sources = sum(s is not None
                      for s in (self.trace, self.indices, self.run,
                                self.kernel))
        sources += self.compiled is not None or self.hlo_text is not None
        if sources != 1:
            raise ValueError(
                f"WorkloadSpec {self.label!r} needs exactly one measurement "
                f"source (trace | indices | run | kernel | compiled/hlo), "
                f"got {sources}")

    # -- derivation -------------------------------------------------------

    def with_(self, **changes) -> "WorkloadSpec":
        """Frozen-friendly variant derivation (sweeps, relabeling)."""
        return dataclasses.replace(self, **changes)

    def grid(self, **axes) -> list["WorkloadSpec"]:
        """Cartesian expansion of this spec over parameter axes.

        Each keyword names a spec field and supplies the values to sweep;
        the product is expanded in the given axis order (last axis fastest)
        and every point is relabeled ``label[k=v,...]`` so sweep reports
        and shift events stay self-describing::

            spec.grid(waves_per_tile=[4, 8, 32], pipeline_depth=[2, 4])
            # -> 6 specs, labels like "solid[waves_per_tile=4,pipeline_depth=2]"

        Pair with ``Session.sweep`` (or ``sweep_grid`` for a device axis).
        """
        for k in axes:
            if k not in {f.name for f in dataclasses.fields(self)}:
                raise ValueError(
                    f"grid axis {k!r} is not a WorkloadSpec field")
        keys = list(axes)
        out = []
        for combo in itertools.product(*(axes[k] for k in keys)):
            changes = dict(zip(keys, combo))
            suffix = ",".join(f"{k}={v}" for k, v in changes.items())
            out.append(self.with_(label=f"{self.label}[{suffix}]", **changes))
        return out

    def fingerprint(self) -> Optional[str]:
        """Content hash of everything a provider's ``collect`` reads.

        Keys the sweep engine's per-point memoization: two specs with the
        same fingerprint yield the same ``CounterSet`` from a (stateless)
        provider, so a repeated grid point or a re-run sweep is served
        from cache.  The label is deliberately *excluded* — it names the
        point but does not change the measurement (the cache relabels).
        Opaque sources (``run`` callables, ``compiled`` artifacts) are not
        hashable by content: returns ``None``, meaning "never memoize".
        Host arrays are hashed in place; one of 8 MiB or more is hashed as
        a sha256 tree of 4 MiB chunks in parallel (``_hash_array``).
        Device-resident kernel parameters (``jax.Array``) are digested on
        the device and never copied to the host (``kernel.digest``): the
        fingerprint then holds their 128-bit device digests
        (``repro.kernels.digest``, not cryptographic), with dtype and
        shape, under the sha256 of the rest.
        """
        if self.run is not None or self.compiled is not None:
            return None
        h = hashlib.sha256()
        on_device: dict = {}
        if self.kernel is not None:
            arrays = _device_arrays(self.kernel.params.values())
            if arrays:
                on_device = {id(a): d for a, d in
                             zip(arrays, _device_digests(arrays))}

        def put(*parts) -> None:
            for part in parts:
                if id(part) in on_device:
                    h.update(b"device-digest")
                    h.update(str(part.dtype).encode())
                    h.update(str(part.shape).encode())
                    h.update(on_device[id(part)])
                elif isinstance(part, np.ndarray):
                    arr = np.ascontiguousarray(part)
                    h.update(str(arr.dtype).encode())
                    h.update(str(arr.shape).encode())
                    _hash_array(h, arr)
                else:
                    h.update(repr(part).encode())
                h.update(b"|")

        if self.trace is not None:
            put("trace", self.trace.degree, self.trace.job_class,
                self.trace.core, self.trace.lanes_active,
                self.trace.waves_per_tile, self.trace.pipeline_depth)
        elif self.indices is not None:
            put("indices", np.asarray(self.indices))
        elif self.kernel is not None:
            put("kernel", self.kernel.op)
            for k in sorted(self.kernel.params):
                v = self.kernel.params[k]
                if id(v) not in on_device and hasattr(v, "shape"):
                    v = np.asarray(v)
                put(k, v)
        elif self.hlo_text is not None:
            put("hlo", self.hlo_text)
        put(self.num_bins, self.job_class, self.waves_per_tile,
            self.pipeline_depth, self.num_cores, self.num_devices,
            self.bytes_read, self.flops, self.overhead_cycles)
        return h.hexdigest()

    def resolve_trace(self) -> counters_mod.WaveTrace:
        """Materialize the wave trace with this spec's geometry applied.

        Runs the kernel for ``kernel``/``run`` sources (the legacy
        acquisition path; ``TraceProvider`` synthesizes ``kernel`` sources
        without a launch instead).  Never mutates the source trace:
        geometry overrides produce a copied-geometry view via
        ``WaveTrace.with_geometry``.
        """
        if self.compiled is not None or self.hlo_text is not None:
            raise ValueError(
                f"WorkloadSpec {self.label!r} has no wave-trace source "
                f"(compiled/HLO specs carry roofline counters only — "
                f"collect them with the 'hlo' provider)")
        if self.trace is not None:
            tr = self.trace
        elif self.run is not None:
            tr = self.run()
        elif self.kernel is not None:
            tr = self.run_kernel()
        else:
            tr = counters_mod.trace_from_indices(
                np.asarray(self.indices), self.num_bins,
                num_cores=self.num_cores, job_class=self.job_class,
                waves_per_tile=self.waves_per_tile or 1,
                pipeline_depth=self.pipeline_depth or 2)
        if self.waves_per_tile is not None or self.pipeline_depth is not None:
            tr = tr.with_geometry(self.waves_per_tile, self.pipeline_depth)
        return tr

    def run_kernel(self) -> counters_mod.WaveTrace:
        """Launch the described instrumented kernel; return its trace.

        A bare ``indices`` stream runs through the instrumented scatter-add
        as unit values, with the geometry defaults of
        ``trace_from_indices`` (waves_per_tile 1), so the 'trace' and
        'kernel' providers agree bit for bit.
        """
        if self.indices is not None:
            return self._run_indices()
        if self.kernel is None:
            raise ValueError(f"WorkloadSpec {self.label!r} has no kernel "
                             f"source")
        p = self.kernel.params
        if self.kernel.op == "histogram":
            from repro.kernels.histogram import ops as hist_ops  # lazy: jax
            _, tr = hist_ops.histogram_instrumented(
                p["img"], variant=p["variant"], force_fao=p["force_fao"],
                weighted=p["weighted"], num_bins=p["num_bins"],
                num_cores=self.num_cores,
                waves_per_tile=self.waves_per_tile,
                pipeline_depth=self.pipeline_depth or 2)
            return tr
        if self.kernel.op == "scatter_add":
            from repro.kernels.scatter_add import ops as scat_ops  # lazy
            _, c = scat_ops.instrumented_scatter_add(
                p["ids"], p["values"], p["num_segments"],
                num_cores=self.num_cores, job_class=p["job_class"],
                waves_per_tile=self.waves_per_tile,
                pipeline_depth=self.pipeline_depth or 2)
            return c["trace"]
        if self.kernel.op == "moe_router":
            return self._run_router()
        raise ValueError(f"unknown kernel op {self.kernel.op!r}")

    def _run_router(self) -> counters_mod.WaveTrace:
        """Route the batch on the device and count its expert loads there:
        one program, the ids never leave the device."""
        import jax  # lazy: jax

        from repro.kernels.scatter_add import ops as scat_ops
        from repro.models import moe

        p = self.kernel.params
        cfg = p["cfg"]
        with _telemetry.span("moe.route", layer=p["layer"]):
            program = scat_ops.count_program(moe.expert_stream(cfg),
                                             cfg.num_experts)
            router = {"w": p["router_w"], "bias": p["router_bias"]}
            router = {k: v if isinstance(v, jax.Array) else jax.device_put(v)
                      for k, v in router.items()}
        _, c = scat_ops.instrumented_count(
            program, p["hidden"], router, num_cores=self.num_cores,
            job_class=p["job_class"], waves_per_tile=self.waves_per_tile,
            pipeline_depth=self.pipeline_depth or 2)
        _ROUTED_TOKENS.inc(p["hidden"].shape[0], layer=str(p["layer"]))
        return c["trace"]

    def _run_indices(self) -> counters_mod.WaveTrace:
        """Count the index stream with the instrumented scatter-add.

        The stream length must be a multiple of the kernel tile: a shorter
        stream would be sentinel-padded by the launch, and the padding
        waves would be *counted* — the measured N/e would then silently
        diverge from the trace provider's (which models the raw stream),
        turning every ``validate()`` into a false alarm.  Refuse instead.
        """
        from repro.kernels.scatter_add import ops as scat_ops  # lazy: jax

        idx = np.asarray(self.indices).reshape(-1)
        tile = scat_ops.sk.DEFAULT_TILE
        if idx.size % tile != 0:
            raise ValueError(
                f"WorkloadSpec {self.label!r}: the 'kernel' provider needs "
                f"an index stream sized to a multiple of the scatter tile "
                f"({tile}); got {idx.size}. Pad the stream, or use "
                f"WorkloadSpec.from_scatter_add (both providers then share "
                f"the kernel's own sentinel padding).")
        _, c = scat_ops.instrumented_scatter_add(
            idx, np.ones(idx.shape, np.float32), self.num_bins,
            num_cores=self.num_cores, job_class=self.job_class,
            waves_per_tile=self.waves_per_tile or 1,
            pipeline_depth=self.pipeline_depth or 2)
        return c["trace"]

    def committed_stream(self) -> tuple[np.ndarray, int, int]:
        """(committed index stream, job class, waves_per_tile).

        The stream the trace provider synthesizes counters from and the
        heat map attributes per bin.  For kernel sources it is the kernel
        family's committed-stream mirror, so the degrees match the
        in-kernel instrumentation exactly (cross-validated by the
        provider-equivalence tests and ``Session.validate``).  Sources
        with no host stream raise ``ValueError``: pre-recorded ``trace``,
        opaque ``run``, HLO, and ``moe_router`` (its ids are made on the
        device).
        """
        if self.indices is not None:
            return (np.asarray(self.indices).reshape(-1), self.job_class,
                    self.waves_per_tile or 1)
        if self.kernel is None:
            raise ValueError(
                f"spec {self.label!r} has no committed index stream to "
                f"attribute (kernel/indices sources only)")
        p = self.kernel.params
        if self.kernel.op == "histogram":
            from repro.kernels.histogram import ops as hist_ops  # lazy: jax
            stream = hist_ops.committed_index_stream(
                p["img"], num_bins=p["num_bins"], variant=p["variant"])
            job_class = hist_ops.histogram_job_class(
                force_fao=p["force_fao"], weighted=p["weighted"])
            return stream, job_class, (
                self.waves_per_tile
                or hist_ops.default_waves_per_tile(p["img"]))
        if self.kernel.op == "scatter_add":
            from repro.kernels.scatter_add import ops as scat_ops  # lazy
            stream = scat_ops.committed_id_stream(
                p["ids"], p["num_segments"])
            return stream, p["job_class"], (
                self.waves_per_tile or scat_ops.default_waves_per_tile())
        if self.kernel.op == "moe_router":
            raise ValueError(
                f"spec {self.label!r}: a moe_router source routes its ids "
                f"on the device, so it has no host index stream; collect "
                f"it with the 'kernel' provider")
        raise ValueError(f"unknown kernel op {self.kernel.op!r}")

    # -- constructors -----------------------------------------------------

    @classmethod
    def from_trace(cls, trace: counters_mod.WaveTrace, *, label: str,
                   **kw) -> "WorkloadSpec":
        return cls(label=label, trace=trace, **kw)

    @classmethod
    def from_indices(cls, indices, num_bins: int, *, label: str,
                     **kw) -> "WorkloadSpec":
        """Synthetic/offline index stream (no kernel run needed)."""
        spec = cls(label=label, indices=np.asarray(indices),
                   num_bins=num_bins, **kw)
        if spec.bytes_read == 0.0:
            spec = spec.with_(bytes_read=float(np.asarray(indices).size * 4))
        return spec

    @classmethod
    def from_histogram(cls, img, *, label: str, variant: str = "hist",
                       force_fao: bool = True, weighted: bool = False,
                       num_bins: int = 256, **kw) -> "WorkloadSpec":
        """Instrumented Pallas histogram launch as the counter source.

        ``bytes_read`` defaults to the image's HBM traffic (1 byte per
        channel, as in the paper's case study).
        """
        spec_kw = dict(kw)
        if "bytes_read" not in spec_kw:
            from repro.kernels.histogram import ops as hist_ops  # lazy: jax
            spec_kw["bytes_read"] = hist_ops.image_bytes(img)
        return cls(label=label,
                   kernel=KernelSource(op="histogram", params={
                       "img": img, "variant": variant,
                       "force_fao": force_fao, "weighted": weighted,
                       "num_bins": num_bins}),
                   **spec_kw)

    @classmethod
    def from_scatter_add(cls, ids, values, num_segments: int, *, label: str,
                         job_class: int = timing.FAO, **kw) -> "WorkloadSpec":
        """Instrumented Pallas scatter-add launch as the counter source."""
        spec_kw = dict(kw)
        spec_kw.setdefault("bytes_read", float(np.asarray(ids).size * 4))
        return cls(label=label,
                   kernel=KernelSource(op="scatter_add", params={
                       "ids": ids, "values": values,
                       "num_segments": num_segments,
                       "job_class": job_class}),
                   **spec_kw)

    @classmethod
    def from_moe_router(cls, layer_params: dict, hidden, cfg, *, label: str,
                        layer: object = 0, job_class: int = timing.FAO,
                        **kw) -> "WorkloadSpec":
        """One MoE layer's expert-load count, routed on the device.

        ``layer_params`` holds the layer's ``"router"`` (``w`` and, for
        sigmoid scoring, ``bias``; the experts are not read), ``hidden``
        the (T, d_model) batch of hidden states the router sees, and
        ``cfg`` the layer's ``repro.models.moe.MoEConfig``.  The kernel
        provider routes the batch and counts the token-major id stream
        (T x top_k ids, unit values, ``num_experts`` segments) in one
        device program.  Keep ``hidden`` and the router on the device
        (``jax.Array``): they are then fingerprinted there and never
        copied.  ``layer`` labels the layer in spans and counters.
        ``bytes_read`` defaults to the count's id reads, 4 bytes an id.
        """
        router = layer_params["router"]
        spec_kw = dict(kw)
        spec_kw.setdefault("bytes_read",
                           float(hidden.shape[0] * cfg.top_k * 4))
        return cls(label=label,
                   kernel=KernelSource(op="moe_router", params={
                       "hidden": hidden, "router_w": router["w"],
                       "router_bias": router.get("bias"), "cfg": cfg,
                       "layer": layer, "job_class": job_class}),
                   **spec_kw)

    @classmethod
    def from_compiled(cls, compiled=None, *, label: str,
                      hlo_text: Optional[str] = None, num_devices: int = 1,
                      **kw) -> "WorkloadSpec":
        """Compiled-step source for the HLO provider (roofline counters).

        Pass a jax compiled artifact (``jit(f).lower(...).compile()``),
        a post-optimization HLO module text, or both (the artifact
        supplies flops/bytes via cost analysis; the text supplies the
        collective traffic).
        """
        return cls(label=label, compiled=compiled, hlo_text=hlo_text,
                   num_devices=num_devices, **kw)
