from repro.kernels.ssd.ops import (ssd_chunk_fused, ssd_decode_inplace,
                                  ssd_scan_fused)

__all__ = ["ssd_chunk_fused", "ssd_decode_inplace", "ssd_scan_fused"]
