"""``paged_decode_attention``'s share of its memory roofline: the bytes
the decode lanes need (K and V of their live positions, query and
output), over the kernel's device time, over the HBM bandwidth."""


def read(run):
    total_ns, count = run.kernel_device()
    need = run.decode_attention_bytes()
    if count == 0 or total_ns == 0 or need == 0:
        return None
    return 100.0 * need / (total_ns * 1e-9) / run.peak("hbm_bytes_per_s")
