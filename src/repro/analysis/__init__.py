from repro.analysis.hlo import collective_stats, count_op
from repro.analysis.roofline import (
    PEAKS,
    RooflineReport,
    analyze,
    estimate_model_flops,
    load_reports,
    peaks,
    save_reports,
)

__all__ = ["collective_stats", "count_op", "analyze", "RooflineReport",
           "PEAKS", "peaks", "estimate_model_flops", "save_reports", "load_reports"]
