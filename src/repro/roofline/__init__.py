from repro.roofline.analysis import (  # noqa: F401
    PEAKS, collective_bytes, dominant_term, hardware, roofline_terms,
)
from repro.roofline.solver import (  # noqa: F401
    loop_corrected, profile_solve_round,
)
