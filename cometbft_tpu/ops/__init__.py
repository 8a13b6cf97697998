"""TPU device kernels (JAX/XLA) — the framework's dense-compute layer.

The only data-parallel compute in a BFT consensus engine is signature
verification (reference: types/validation.go:153-257, the batch verifier at
crypto/ed25519/ed25519.go:208-241). Here it becomes a lane-parallel device
program: each TPU vector lane verifies one Ed25519 signature under ZIP-215
semantics, producing a per-lane validity mask (the reference needs a serial
re-verify fallback to pinpoint bad signatures; on TPU the mask is free).

Layout:
  limbs.py            host-side numpy packing: bytes/ints <-> limb arrays
  field.py            GF(2^255-19) arithmetic, radix-2^13 x 20 limbs, int32
  curve.py            edwards25519 point ops, decompression, Straus ladder
  ed25519_kernel.py   jitted batch-verify entry + host glue (hashing, padding)
"""
