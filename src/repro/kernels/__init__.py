# Pallas kernels for the compute hot-spot the paper optimizes: guided
# tile scoring (guided_score.py), with its pure-jnp oracle in ref.py.
