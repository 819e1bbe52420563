"""Share of its roofline that the paged_attention kernel reached in the
traced window, in % (kernels/paged_attention.py counts its work)."""
from readers import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "paged_attention")
