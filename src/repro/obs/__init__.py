from .trace import Tracer, NULL_TRACER, jax_profile                   # noqa: F401
from .registry import (Counter, Gauge, Histogram,                     # noqa: F401
                       MetricsRegistry, log_buckets)
from .report import acceptance_report, format_acceptance_report       # noqa: F401
from .quality import ENTROPY_BINS, PageHinkley, QualityStats          # noqa: F401
from .sketch import GKSketch, SLOConfig, SLOTracker                   # noqa: F401
from .recorder import FlightRecorder                                  # noqa: F401
