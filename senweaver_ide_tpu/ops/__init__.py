from .attention import attention, causal_mask, repeat_kv
from .flash_attention import flash_attention
from .norms import rms_norm
from .rotary import apply_rope, rope_cos_sin, rope_frequencies
from .sampling import apply_temperature, apply_top_k, apply_top_p, sample_token
