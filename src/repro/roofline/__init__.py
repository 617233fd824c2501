from .analysis import (PEAKS, peaks, collective_bytes_from_hlo, model_flops,
                       roofline_terms)

__all__ = ["PEAKS", "peaks", "collective_bytes_from_hlo", "model_flops",
           "roofline_terms"]
