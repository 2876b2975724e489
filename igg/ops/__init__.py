"""Hand-optimized TPU kernels for the hot ops (Pallas).

The reference's "native surface" is its CUDA pack/unpack kernels and SIMD
copies (`/root/reference/src/update_halo.jl:439-462,555-563`); on TPU the
equivalent layer is Pallas kernels that fuse the stencil update with halo
maintenance so each time step touches HBM exactly once per array.
"""

from .diffusion_pallas import (
    diffusion_compute,
    fused_diffusion_step,
    fused_diffusion_steps,
    pallas_supported,
)
from .stencil import interior_add
from .hm3d_pallas import (fused_hm3d_step, fused_hm3d_steps,
                          hm3d_pallas_supported)
from .stokes_pallas import fused_stokes_iteration, stokes_pallas_supported
from .stokes_trapezoid import (fit_stokes_K, fused_stokes_trapezoid_iters,
                               stokes_trapezoid_supported)
from .hm3d_trapezoid import (fit_hm3d_K, fused_hm3d_trapezoid_steps,
                             hm3d_trapezoid_supported)
from .wave2d_pallas import (fit_wave2d_K, fused_wave2d_chunk_steps,
                            fused_wave2d_step, fused_wave2d_steps,
                            wave2d_chunk_supported, wave2d_pallas_supported)

__all__ = ["diffusion_compute", "fit_hm3d_K", "fit_stokes_K",
           "fit_wave2d_K", "fused_diffusion_step", "fused_diffusion_steps",
           "fused_hm3d_step", "fused_hm3d_steps",
           "fused_hm3d_trapezoid_steps", "fused_stokes_iteration",
           "fused_stokes_trapezoid_iters", "fused_wave2d_chunk_steps",
           "fused_wave2d_step", "fused_wave2d_steps",
           "hm3d_pallas_supported", "hm3d_trapezoid_supported",
           "interior_add", "pallas_supported", "stokes_pallas_supported",
           "stokes_trapezoid_supported", "wave2d_chunk_supported",
           "wave2d_pallas_supported"]
