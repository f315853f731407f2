"""Audio alignment (the paper's batch-of-queries scenario): align a batch
of energy tracks against a longer reference track with batched sDTW,
then show the differentiable soft-sDTW loss pulling a query toward a
target track.

The tracks are seeded Cylinder-Bell-Funnel series (``repro.data.cbf``),
standing in for per-frame energy envelopes of recorded audio.

  PYTHONPATH=src python examples/audio_align.py
"""

import numpy as np
import jax
import jax.numpy as jnp

import repro
from repro.data.cbf import make_cylinder_bell_funnel
from repro.train import make_sdtw_loss

B, S = 4, 48
tracks = jnp.asarray(make_cylinder_bell_funnel(np.random.default_rng(0),
                                               B, S))         # (B, S)

# 1) align each track against a longer reference track (track 0, tiled)
reference = jnp.tile(tracks[0], 4)                            # (4S,)
res = repro.sdtw(tracks, reference, outputs=("cost", "start", "end"))
print("alignment costs vs reference (track 0 should match itself ~0):")
for i in range(B):
    print(f"  track {i}: cost={float(res.cost[i]):8.3f} "
          f"window=[{int(res.start[i])}..{int(res.end[i])}]")
assert float(res.cost[0]) <= float(jnp.min(res.cost[1:])) + 1e-3

# 2) soft-sDTW as a differentiable alignment loss
target = tracks[0]
query = tracks[1]
soft_loss = make_sdtw_loss(target, gamma=0.5, normalize=False)
loss_fn = lambda q: soft_loss(q[None])
g = jax.grad(loss_fn)(query)
first = float(loss_fn(query))
print(f"\nsoft-sDTW loss={first:.3f} "
      f"|grad|={float(jnp.linalg.norm(g)):.3f} (differentiable: OK)")
lr = 0.1
for step in range(10):
    query = query - lr * jax.grad(loss_fn)(query)
last = float(loss_fn(query))
print(f"after 10 grad steps: loss={last:.3f} (should drop)")
assert last < first
