"""psi_matmul: least time of the calls served (weights once at format
width, activations, 2*M*K*N at the int8 peak) over the kernel device time (%)."""
from chipbench import readers


def read(run):
    return readers.psi_matmul_roofline(run)
