"""Plain big-integer BLS12-381 (fields, curves, pairing, hash-to-curve,
point encoding): the benchmark's own copy of the program's pure-Python
oracle (`lodestar_tpu/crypto/bls/`), taken at PR 25 so that no later PR
can change the yardstick. It imports nothing of the program."""
