"""The benchmark's own code: generators, reference, reductions, driver.

Nothing here is imported by the program; only `product.py` imports it."""
