from repro.kernels.glm_hvp.glm_hvp import glm_hvp_pallas
from repro.kernels.glm_hvp.ops import glm_hvp
from repro.kernels.glm_hvp.ref import glm_hvp_ref
