"""Unit tests for ``utils/hlo.collective_bytes`` — the byte counter under
the gradient-sync byte-cut tests. Synthetic HLO lines
mirror the forms observed in real compiled programs (sync tuple all-reduces
with ``/*index=N*/`` comments, async -start/-done pairs, iota and explicit
replica groups)."""

from distributeddeeplearning_tpu.utils.hlo import collective_bytes


def test_sync_tuple_allreduce_sums_all_elements():
    txt = ("%all-reduce.1 = (f32[64]{0}, f32[3,3,16,16]{3,2,1,0}, "
           "/*index=5*/f32[256]{0}) all-reduce(%a, %b, %c), "
           "replica_groups=[1,8]<=[8], to_apply=%add")
    got = collective_bytes(txt, 8)
    assert got["all-reduce"] == [(4 * (64 + 3 * 3 * 16 * 16 + 256), 8)]


def test_async_start_counts_payload_only_and_done_not_at_all():
    # The -start tuple is (operand, result, scratch/flags...): summing
    # would double-count, and "last element" reads a 4-byte u32 flag on
    # TPU permute-starts (observed in the gpt2_owt lowering, where the
    # grad reduce-scatter decomposes into 224 permutes). The LARGEST
    # element is the payload for every kind.
    txt = "\n".join([
        "%ags = (bf16[128]{0}, bf16[1024]{0}) all-gather-start(%x), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}",
        "%agd = bf16[1024]{0} all-gather-done(%ags)",
        "%cps = (bf16[192,12,64]{0,2,1}, bf16[192,12,64]{0,2,1}, "
        "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%b), "
        "channel_id=6, source_target_pairs={{0,1},{1,0}}",
        "%cpd = bf16[192,12,64]{0,2,1} collective-permute-done(%cps)",
    ])
    got = collective_bytes(txt, 8)
    assert got["all-gather"] == [(2 * 1024, 8)]
    assert got["collective-permute"] == [(2 * 192 * 12 * 64, 8)]


def test_explicit_and_iota_groups_and_default():
    txt = "\n".join([
        "%ar1 = f32[100]{0} all-reduce(%a), replica_groups={{0,1},{2,3}}, "
        "to_apply=%add",
        "%ar2 = f32[100]{0} all-reduce(%b), replica_groups=[2,4]<=[8], "
        "to_apply=%add",
        "%cp = f32[100]{0} collective-permute(%c), "
        "source_target_pairs={{0,1}}",
    ])
    got = collective_bytes(txt, 8)
    assert got["all-reduce"] == [(400, 2), (400, 4)]
    # No replica_groups on the permute: defaults to n_devices.
    assert got["collective-permute"] == [(400, 8)]


def test_sync_reduce_scatter_normalized_to_full_input():
    # The sync form's definition type is the SCATTERED output (full/group);
    # the async -start tuple's largest element is the full input. Both must
    # report the full-input bytes, or the same program's RS traffic shrinks
    # ~group_size-fold depending on which form the backend emitted.
    sync = ("%rs = f32[128]{0} reduce-scatter(%x), "
            "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add")
    astart = "\n".join([
        "%rss = (f32[1024]{0}, f32[128]{0}) reduce-scatter-start(%x), "
        "replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add",
        "%rsd = f32[128]{0} reduce-scatter-done(%rss)",
    ])
    got_sync = collective_bytes(sync, 8)
    got_async = collective_bytes(astart, 8)
    assert got_sync["reduce-scatter"] == [(4 * 1024, 8)]
    assert got_async["reduce-scatter"] == [(4 * 1024, 8)]


def test_non_collective_lines_ignored():
    txt = ("%fusion.1 = f32[64]{0} fusion(%p), kind=kLoop, "
           "calls=%fused_computation")
    got = collective_bytes(txt, 8)
    assert all(not v for v in got.values())
