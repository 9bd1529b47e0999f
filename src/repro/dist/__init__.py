"""repro.dist — explicit placement layer (mesh + partition rules).

The models stay mesh-agnostic: they call ``ctx.constrain(x, name)`` with a
small rule-name vocabulary (``residual``, ``heads``, ``tokens``,
``ffn_hidden``, ``logits``, ``scores``, ``kv/*``) and the
launch layer decides what those names mean for the mesh at hand by entering
``ctx.activation_sharding_ctx(sharding.make_activation_rules(mesh, cfg))``.
Outside the context every constraint is a transparent no-op, so kernels and
models import nothing mesh-specific.

``sharding`` holds the pure PartitionSpec logic (no devices required — it
works on ``jax.sharding.AbstractMesh``).
"""
from . import ctx, sharding

__all__ = ["ctx", "sharding"]
