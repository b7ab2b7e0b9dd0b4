"""``redmule_gemm``'s share of its roofline: each product the traced steps
require at its own roofline, over the kernel's device time; in percent."""
import readers
import work


def read(rec):
    return readers.kernel_share(
        rec, "redmule_gemm", lambda s: work.gemms_roofline_s(s["gemms"], rec["peak"]))
