"""Operations and bytes of one ``eva_fused`` launch, from its shapes.

The kernel (the program's fused Eva precondition -> momentum -> trust-region
partials, one launch per bucket) takes G (L, d_in, d_out) in the gradient's
dtype, ā (L, d_in), b̄ (L, d_out) and the f32 momentum m (L, d_in, d_out),
and writes the f32 output (L, d_in, d_out) and three partial sums per item.

What the algorithm must move: G read once, m read once, the output written
once, and the two vectors.  (The kernel reads G twice -- once for the
bilinear coefficient, once for the update -- so its own traffic is higher;
counting the minimum keeps the roofline share an honest upper bound.)

Operations per element: the bilinear form āᵀGb̄ (3), the rank-one update
(G - c ā b̄ᵀ)/γ (4), the momentum fold μm + P (2) and the three partial sums
<out, G>, <out, out>, <G, G> (6).
"""
import re

FLOPS_PER_ELEMENT = 15
_SHAPE = re.compile(r'(bf16|f32|f16)\[(\d+),(\d+),(\d+)\]')
_BYTES = {'bf16': 2, 'f16': 2, 'f32': 4}


def cost(L: int, d_in: int, d_out: int, g_bytes: int) -> tuple:
    """(FLOPs, bytes) of one launch over an (L, d_in, d_out) bucket."""
    n = L * d_in * d_out
    return (FLOPS_PER_ELEMENT * n,
            n * (g_bytes + 4 + 4) + 4 * L * (d_in + d_out))


def cost_of_event(text: str) -> tuple:
    """(FLOPs, bytes) from a trace event's HLO text
    ``%eva_fused_stacked.N = (f32[L,m,n]{..}, ..) custom-call(bf16[L,m,n]{..} ..``:
    the output gives the bucket's shape, the first operand G's dtype."""
    out_part, _, operands = text.partition('custom-call(')
    L, m, n = map(int, _SHAPE.search(out_part).groups()[1:])
    g_dtype = _SHAPE.search(operands).group(1)
    return cost(L, m, n, _BYTES[g_dtype])
