"""Share of its roofline that the approx_matmul kernel reached in the traced
window, in % (kernels/approx_matmul.py counts its work)."""
from readers import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "approx_matmul")
