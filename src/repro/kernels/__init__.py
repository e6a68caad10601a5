# Pallas kernels of the jax solve tiers: the water-filling feasibility
# reduction (waterfill.py, core/jax_solve.py) and the pairwise envy gaps
# (envy.py, core/jax_coop.py), each beside its jnp reference.
