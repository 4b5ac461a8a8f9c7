"""Single-device attention, one core a file (the cross-device half is
`parallel.ring_attention`). One contract: q, k (B, T, H, D), v (B, T, H, Dv)
-> (B, T, H, Dv); k and v may have a divisor of q's heads and are never
repeated. Three tiers: "dense" (the reference, in the inputs' type), "chunked"
(XLA, float32 scores, O(T) memory, every backend, differentiable: `xla`) and
"flash" (Pallas kernels, float32 scores in VMEM only). The cores: plain and
banded (`flash`), latent (`latent`), windowed-and-summarised (`eva`), all over
ONE fold, tile rule and grid builder (`fold`), and the differential one
(`diff`: two of the plain or banded forwards a layer over keys and values
laid out once); `rotary` rotates; `layout` owns
the tier that runs, the layout the kernels read and the projections that
produce it; `modules` is what a model calls. A rule by shape has one home
module and is looked up there at call time: a test patches one attribute."""

from ...parallel.ring_attention import dense_attention
from . import diff, eva, flash, fold, latent, layout, modules, rotary, xla
from .diff import differential_attention, key_pairs
from .eva import eva_attention, eva_summaries, eva_tile_pairs
from .flash import (band_tile_pairs, band_tiles, causal_attention,
                    flash_attention)
from .fold import flash_tiles
from .latent import latent_attention
from .layout import (HeadsDense, HeadsOut, head_projection, out_projection,
                     tier)
from .modules import SelfAttention, decoder_attention
from .rotary import (rotary_heads, rotary_in_lanes, rotary_lanes_whole,
                     rotary_positions, rotary_xla)
from .xla import chunked_attention

__all__ = ["dense_attention", "chunked_attention", "flash_attention",
           "flash_tiles", "causal_attention", "band_tiles", "band_tile_pairs",
           "latent_attention",
           "eva_summaries", "eva_attention", "eva_tile_pairs",
           "rotary_in_lanes",
           "rotary_lanes_whole", "HeadsDense", "HeadsOut", "SelfAttention"]
