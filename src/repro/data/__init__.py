from repro.data.cbf import make_cylinder_bell_funnel, make_sdtw_dataset

__all__ = ["make_cylinder_bell_funnel", "make_sdtw_dataset"]
