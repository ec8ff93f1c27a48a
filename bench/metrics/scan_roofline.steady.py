"""scan_roofline.steady (kernels): least time of the exact groups' masked top-k work over the device time of the scan programs, %, in steady cells."""
from harness.readers import scan_roofline


def read(v):
    return scan_roofline(v)
