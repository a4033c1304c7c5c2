"""Operations and bytes of the kernel Falcon-H1 brought
(``ops/pallas/ssm_row_update``), for its share of the roofline. The least
a call needs: every state row crosses HBM once each way, whatever the
live count (the decode step runs all slots)."""


def ssm_row_update_call(slots: int, heads: int, head_dim: int, d_state: int,
                        groups: int) -> dict:
    """One call of ``ssm_row_update``: a layer's state rows of every slot.
    An entry of a row is decayed, takes its share of ``dx (x) B`` (a
    product and a sum) and meets C in ``y`` (a product and a sum): 5
    operations. The rows come in and go out in float32; beside them the
    decay (a head), ``dx`` in and ``y`` out (a head's ``head_dim``), B and
    C (a group's ``d_state``), all float32."""
    entries = slots * heads * head_dim * d_state
    small = slots * (heads + 2 * heads * head_dim + 2 * groups * d_state)
    return {"flops": 5.0 * entries, "bytes": 4.0 * (2 * entries + small)}
