"""paged_decode_roofline: the least time the chip needs for the paged
decode kernel's calls in the traced steps (the larger of their bytes over
HBM bandwidth and their FLOPs over peak), over the device time of the
kernel's events, in percent. Bytes bound it at these shapes."""
import roofline


def read(facts):
    tr = facts["trace"]
    if not tr or tr["paged_decode_calls"] == 0 or tr["paged_decode_s"] <= 0:
        return None
    need = roofline.roofline_s(tr["paged_decode_flops"],
                               tr["paged_decode_bytes"], tr["peaks"])
    return 100.0 * need / tr["paged_decode_s"]
