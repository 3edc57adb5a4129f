from .analysis import (PEAKS, collective_bytes_from_hlo, model_flops,
                       peaks, roofline_report)

__all__ = ["PEAKS", "peaks", "collective_bytes_from_hlo",
           "roofline_report", "model_flops"]
