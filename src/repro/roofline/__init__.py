from repro.roofline.hlo import collective_bytes, split_computations
from repro.roofline.terms import (
    DCN_LINK_BW,
    DEVICE_PEAKS,
    HBM_BW,
    ICI_LINK_BW,
    PEAK_FLOPS_BF16,
    RooflineTerms,
    compute_terms,
    device_peaks,
    elastic_presence,
    meta_wire_bytes,
    model_flops,
    participant_wire_bytes,
    topology_wire_bytes,
)
