"""Keys on Thearling's entropy ladder: a uniform draw AND-ed with
``config["ands"]`` further uniform draws (``ands=0``: uniform keys).  The
arithmetic of ``repro.data.distributions.entropy_keys``, done on the device;
for 32-bit keys 0..3 ANDs give 32.00, 25.95, 17.41 and 10.78 bits."""
import jax


def make(rng, n, dtype, config):
    draws = jax.random.split(rng, int(config["ands"]) + 1)
    keys = jax.random.bits(draws[0], (n,), dtype)
    for d in draws[1:]:
        keys = keys & jax.random.bits(d, (n,), dtype)
    return keys
