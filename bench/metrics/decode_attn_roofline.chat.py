"""``paged_flash_decode``'s share of its roofline: the KV bytes of the live
contexts (and the query and output) at full bandwidth, or the scores and
values at peak, over the kernel's device time; in percent."""
import readers
import work


def read(rec):
    return readers.kernel_share(
        rec, "paged_flash_decode", lambda s: work.roofline_s(*s["attn"], rec["peak"]),
        kinds=("decode",))
