"""logparser_tpu — a TPU-native access-log dissection framework.

A from-scratch rebuild of the capabilities of nielsbasjes/logparser
(/root/reference): the LogFormat configuration string is the schema; a
demand-driven dissector graph produces exactly the typed fields the user asks
for.  Unlike the reference (one compiled regex per line + reflection setters),
each LogFormat here compiles to a static field-extraction program executed over
``[batch, line_len]`` uint8 buffers on TPU, with vectorized post-stages and
columnar outputs; an exact host ("oracle") execution path provides per-line
parsing and the bit-exactness baseline.
"""

__version__ = "0.1.0"

from .observability import (  # noqa: F401
    CappedLogger,
    CounterRegistry,
    Histogram,
    MetricsRegistry,
    counters,
    disable_stage_annotations,
    enable_stage_annotations,
    log_warning_once,
    metrics,
    observe_stage,
    pipeline_stage,
    suppressed_warning_counts,
    version_banner,
)
from .core import (  # noqa: F401
    Cast,
    DissectionFailure,
    Dissector,
    InvalidDissectorException,
    MissingDissectorsException,
    Parser,
    SetterPolicy,
    SimpleDissector,
    Value,
    field,
)
