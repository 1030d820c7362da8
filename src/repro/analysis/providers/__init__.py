"""Pluggable counter-acquisition backends for the analysis Session.

One acquisition API for modeled, measured, and HLO-derived counters::

    Session(device="v5e", provider="kernel").classify(spec)
    Session(device="v5e").validate(spec, providers=("trace", "kernel"))

See ``base`` for the ``CounterProvider`` protocol and registry, and the
sibling modules for the four shipped providers.
"""

from repro.analysis.providers.base import (  # noqa: F401
    PROVIDERS,
    CounterProvider,
    collect_batch_fallback,
    collection_origin,
    get_provider,
    provider_collect_batch,
    register_provider,
)
from repro.analysis.providers.fault import (  # noqa: F401
    FaultInjectionProvider,
    InjectedFault,
)
from repro.analysis.providers.hlo import HloProvider  # noqa: F401
from repro.analysis.providers.kernel import (  # noqa: F401
    InstrumentedKernelProvider,
)
from repro.analysis.providers.microbench import MicrobenchProvider  # noqa: F401
from repro.analysis.providers.trace import TraceProvider  # noqa: F401
from repro.core.counters import CounterSet  # noqa: F401
