"""``bloom_probe`` (``kernels/bloom.py``): test N keys against a bitset.

What the algorithm needs per call, from the op's shapes (bitset operand
``f32[R, 128]`` = R*128 bits, keys operand ``s32[N]``): read the N keys
(4 bytes each) and the bitset once (bits / 8 bytes), write one byte per
key; per key and hash function (2, the pipeline's setting) a multiply,
add, shift, xor and modulo to hash, and a bit test and AND: 7 integer
ops.  Integer vector ops have no published peak on this chip, so the
compute bound uses its published op rate (``flops_per_s``); the memory
bound is the larger by orders of magnitude.
"""
from bench.roofline.shapes import result_and_operands

NAMES = ("bloom_probe",)
OPS_PEAK = "flops_per_s"
NUM_HASHES = 2
OPS_PER_KEY_HASH = 7


def cost(event, run):
    _, operands = result_and_operands(event.name)
    (_, bitset), (_, keys) = operands[0], operands[1]
    n = keys[0]
    bits = bitset[0] * bitset[1]
    ops = n * NUM_HASHES * OPS_PER_KEY_HASH
    nbytes = 4 * n + bits // 8 + n
    return ops, nbytes
