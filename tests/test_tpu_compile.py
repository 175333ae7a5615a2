"""Main-path programs, asked of the TPU compiler at SF1 shapes without a chip.

The TPU's compiler is installed here and compiles for a v5e chip that is
described, not attached (on-chip-measurement guide, section 2). Nothing
runs: a case passes when the chip's compiler accepts the engine's own
program at the width chip_smoke.py runs it. The topology is described
inside a module-scoped fixture (only one process may load libtpu, so never
at import), and the persistent compile cache is off around the compiles
(an entry written here cannot be read back without a chip).

All cases live in this one file: a second file could land on another
xdist worker, whose fixture would then skip every case in silence.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

BATCH = 1 << 20          # sql.batchSizeRows: one SF1 batch capacity


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(fn, *args):
    """Lower and compile `fn` for the described chip. Engine code that
    asks jax.default_backend() while it is traced is steered here, in the
    test, onto the branch it takes on the chip (ops/sortkeys.lexsort)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _at_rows(tree, cap0, rows, sharding):
    """Shapes of `tree` (a small batch's cvs/mask at capacity `cap0`)
    scaled to `rows`: row-indexed leaves, offsets (rows+1) and string
    byte buffers grow with the batch."""
    def leaf(x):
        n = x.shape[0]
        n = rows + 1 if n == cap0 + 1 else max(n * rows // cap0, n)
        return jax.ShapeDtypeStruct((n,) + x.shape[1:], x.dtype,
                                    sharding=sharding)
    return jax.tree.map(leaf, tree)


def _planned(session, qn, kind):
    """q`qn` over small HBM-cached tables, planned by the engine: the
    first exec node of class `kind` and one batch of the cached scan at
    the foot of its first-child chain, as that plan scans it (the views
    of the columns the query reads, plan/optimizer.py:prune)."""
    from spark_rapids_tpu.plan.planner import Planner
    from spark_rapids_tpu.workloads import tpch
    dfs = {"lineitem": tpch.gen_lineitem(0.001, 0, True),
           "orders": tpch.gen_orders(0.001, 1, True),
           "customer": tpch.gen_customer(0.001, 2, True)}
    dfs = {k: session.create_dataframe(v).cache() for k, v in dfs.items()}
    root = Planner(session.conf).plan(tpch.queries()[qn](dfs)._plan)
    stack = [root]
    while stack:
        node = stack.pop()
        if type(node).__name__ == kind:
            scan = node
            while scan.children:
                scan = scan.children[0]
            return node, scan.batches[0]
        stack.extend(node.children)
    raise AssertionError(f"no {kind} in q{qn}'s plan")


def test_q6_step_8mi_rows(one_chip):
    """The q6 step of __graft_entry__.entry() at 8 Mi rows of FLOAT64."""
    import __graft_entry__ as graft
    step, example = graft.entry()
    n = 8 << 20
    args = [jax.ShapeDtypeStruct((n,), jnp.asarray(a).dtype,
                                 sharding=one_chip) for a in example]
    _compile(step, *args)


def test_q1_partial_aggregate_update_1mi_rows(session, one_chip):
    """q1's first-pass aggregate program (HashAggregateExec, tag
    'hash_update': filter + projections + bucketed hash aggregate, one
    dispatch per lineitem batch) at one SF1 batch capacity. At SF1 the
    planner splits q1 into partial / exchange / final (input above 2 Mi
    rows, plan/planner.py); the small tables here plan 'complete', so the
    test rebuilds the same node in partial mode from the planned node's
    own child, keys and aggregates. q1 plans no FusedStageExec: the
    filter chain collapses into this program."""
    from spark_rapids_tpu.config import AGG_STRING_HASH_KEYS
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    agg, small = _planned(session, 1, "HashAggregateExec")
    child = agg.children[0]
    partial = HashAggregateExec(child, agg.key_names, agg.keys,
                                agg.agg_names, agg.aggs, child.schema,
                                mode="partial")
    partial._resolve_fusion()
    update = partial._hash_update_fn(
        partial._batch_nchunks(small),
        partial._has_string_keys()
        and bool(session.conf.get(AGG_STRING_HASH_KEYS)))
    cvs, mask = _at_rows((tuple(small.cvs()), small.row_mask),
                         small.capacity, BATCH, one_chip)
    _compile(update, cvs, mask)


def test_q3_topk_sort_64bit_keys(session, one_chip):
    """q3's SortExec program (lexsort on revenue DESC — a two-limb
    decimal key of 64-bit words — then o_orderdate) at the capacity its
    SF1 aggregate emits."""
    from spark_rapids_tpu.exec.sort import sort_batch_cvs
    sort, _ = _planned(session, 3, "SortExec")
    cap = 1 << 17
    cvs = []
    for f in sort.children[0].schema.fields:
        assert not f.dtype.is_variable_width
        limbs = (2,) if getattr(f.dtype, "is_decimal128", False) else ()
        cvs.append((jax.ShapeDtypeStruct((cap,) + limbs, f.dtype.np_dtype,
                                         sharding=one_chip),
                    jax.ShapeDtypeStruct((cap,), jnp.bool_,
                                         sharding=one_chip)))
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one_chip)
    from spark_rapids_tpu.ops.kernel_utils import CV
    _compile(lambda c, m: sort_batch_cvs([CV(*x) for x in c], m,
                                         sort.orders, (0, 0)), cvs, mask)


def test_exchange_map_every_fixed_width_type(one_chip):
    """The one-chip exchange map (`ShuffleExchangeExec`, tag 'map') over
    every fixed-width type a column can hold. The payload rides a sort
    as 32-bit words, and the chip's compiler has no f64 bitcast (it
    refused this program while float64 was made words, PR 32): a DOUBLE
    column rides a sort of its own type. XLA:CPU accepts either form, so
    only this compile tells them apart."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.expr.expressions import BoundRef
    from spark_rapids_tpu.ops.kernel_utils import CV
    cap = 1 << 13

    def col(dtype, *tail):
        return CV(jax.ShapeDtypeStruct((cap,) + tail, dtype,
                                       sharding=one_chip),
                  jax.ShapeDtypeStruct((cap,), jnp.bool_,
                                       sharding=one_chip))

    cvs = [col(jnp.int64), col(jnp.float64), col(jnp.float64),
           col(jnp.int64, 2), col(jnp.float32), col(jnp.int32),
           col(jnp.int16), col(jnp.int8), col(jnp.bool_)]
    fn = ShuffleExchangeExec._build_map_fn(8, [BoundRef(0, dt.INT64)])
    _compile(fn, cvs, jax.ShapeDtypeStruct((cap,), jnp.bool_,
                                           sharding=one_chip))


def test_q18_sorted_aggregate_update_1mi_rows(session, one_chip):
    """q18's first aggregate (sum of a decimal(12,2) by an int64 key)
    through the sort-segmented update (`HashAggregateExec`, tag 'update')
    at one SF1 batch: the chip's program holds six two-operand sorts (the
    key order by riding: three, the rank, the ride into key order, the
    ride to the slots), no gather and no scatter (PR 36: the parent's
    held five scatters and eleven row-long gathers). The merge is the
    same body over the partial's columns."""
    from decimal import Decimal

    import pyarrow as pa
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.ops.kernel_utils import CV
    plan = session.create_dataframe({
        "k": pa.array([1, 2], pa.int64()),
        "v": pa.array([Decimal(1), Decimal(2)], pa.decimal128(12, 2))}) \
        .group_by("k").agg(F.sum("v").alias("s"))
    stack = [plan._execute()[0]]
    while not isinstance(stack[-1], HashAggregateExec):
        stack.extend(stack.pop().children)
    node = stack[-1]
    node._resolve_fusion()

    def col(dtype):
        return CV(jax.ShapeDtypeStruct((BATCH,), dtype, sharding=one_chip),
                  jax.ShapeDtypeStruct((BATCH,), jnp.bool_,
                                       sharding=one_chip))
    compiled = _compile(
        node._update_fn((0,)), [col(jnp.int64), col(jnp.int64)],
        jax.ShapeDtypeStruct((BATCH,), jnp.bool_, sharding=one_chip))
    text = compiled.as_text()
    assert text.count(" sort(") == 6
    assert " gather(" not in text and " scatter(" not in text


def test_q9_amount_stage_of_128bit_decimals_512ki_rows(session, one_chip):
    """q9's amount, l_extendedprice * (1 - l_discount) - ps_supplycost *
    l_quantity: a decimal(25,4) product and a decimal(26,4) difference a
    row, in limbs (ops/decimal128.py) where the chip emulates 64-bit
    lanes. The fused stage that holds it is the program whose name ends
    in `_d128` (exec/base.py:D128_MARK), with its count of live rows;
    here at 512 Ki rows, past what a partition of the SF1 join puts
    through it (about 325,000 rows in all)."""
    from spark_rapids_tpu.exec.join import HashJoinExec
    from spark_rapids_tpu.plan.planner import Planner
    from spark_rapids_tpu.workloads import tpch
    dfs = {k: session.create_dataframe(v).cache()
           for k, v in tpch.gen_all(0.001, seed=3).items()}
    stack = [Planner(session.conf).plan(tpch.queries()[9](dfs)._plan)]
    while not stack[-1].d128_exprs():
        stack.extend(stack.pop().children)
    stage = stack[-1]
    assert type(stage).__name__ == "FusedStageExec"
    assert stage.d128_exprs() == 2
    assert stage._jit.base_key[1:3] == ("FusedStageExec", "run_d128")
    small = HashJoinExec._concat_batches([], stage.children[0].schema)
    cap0 = small[1].shape[0]
    cvs, mask = _at_rows((tuple(small[0]), small[1]), cap0, 1 << 19, one_chip)
    stats = jax.ShapeDtypeStruct((len(stage.members) + 1,), jnp.int64,
                                 sharding=one_chip)
    _compile(stage._jit._fn, list(cvs), mask, stats)


def test_q9_packed_pair_join_sort_1mi_and_probe_64ki(session, one_chip):
    """q9's two-key join against partsupp, (l_partkey, l_suppkey) =
    (ps_partkey, ps_suppkey): both int64 keys pack into one uint64 word
    over the build side's ranges (exec/join.py:_key_word), so the build
    side sorts once (`buildsort`, 1 Mi rows: partsupp's 800,000 at SF1)
    and each stream batch probes it by binary search (`probe`, 64 Ki
    rows). The parent sorted build and stream rows together for every
    stream batch (`count`, five words a row)."""
    import pyarrow as pa
    from spark_rapids_tpu.exec.join import HashJoinExec
    from spark_rapids_tpu.expr.expressions import col
    from spark_rapids_tpu.ops.kernel_utils import CV
    def two(x, y):
        return session.create_dataframe({x: pa.array([1, 2], pa.int64()),
                                         y: pa.array([3, 4], pa.int64())})
    df = two("a", "b").join(two("c", "d"),
                            on=(col("a") == col("c")) & (col("b") == col("d")))
    stack = [df._execute()[0]]
    while not isinstance(stack[-1], HashJoinExec):
        stack.extend(stack.pop().children)
    node = stack[-1]
    assert node._pack_ok() and not node._fast_path_ok()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def keys(n):
        return [CV(sds((n,), jnp.int64), sds((n,), jnp.bool_))
                for _ in range(2)]
    lo, hi = sds((2,), jnp.int64), sds((2,), jnp.int64)
    cap_b, cap_s = BATCH, 1 << 16
    sort = _compile(HashJoinExec._build_sort_fn(node.rkeys[0].dtype),
                    keys(cap_b), sds((cap_b,), jnp.bool_), lo, hi)
    assert sort.as_text().count(" sort(") == 3  # the pin's byte, two words
    _compile(node._probe_fn(cap_b, cap_s), sds((cap_b,), jnp.uint64),
             sds((), jnp.int32), keys(cap_s), sds((cap_s,), jnp.bool_),
             lo, hi)


@pytest.mark.parametrize("case", ["unpack", "flba_dict_then_plain",
                                  "int32_dict", "int64_small_dict"])
def test_parquet_decode_programs_1mi_rows(one_chip, case):
    """The device Parquet decode of a 1 Mi-row chunk (ops/parquet_decode)
    as the two Parquet cells run it: the bit unpack (the width as
    data, 8 Ki rows of 128 values), `l_extendedprice` (a 6-byte FLBA
    decimal whose dictionary gives way to PLAIN pages), `l_shipdate` (a
    4 Ki-entry dictionary: a gather) and a small dictionary (compare-
    select). The value layouts keep byte matrices lane-dense: a
    [values, width] byte layout padded each value to 128 lanes and the
    compiler took 20 s and 0.8 GB of scratch for one unpack."""
    import functools

    from spark_rapids_tpu.ops import parquet_decode as pd

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cap = BATCH
    pieces = (s((4, pd.DENSE_PIECES), jnp.int32), s((), jnp.int32))
    if case == "unpack":
        _compile(pd.pq_unpack, s((2 * cap,), jnp.int32),
                 s((cap // 128, pd.ROW_BYTES), jnp.uint8),
                 s((), jnp.int32), s((), jnp.int32))
        return
    kind, width, dtype, ndict, merge = {
        "flba_dict_then_plain": ("FIXED_LEN_BYTE_ARRAY", 6, "int64",
                                 1 << 18, True),
        "int32_dict": ("INT32", 4, "int32", 1 << 12, False),
        "int64_small_dict": ("INT64", 8, "int64", 64, False)}[case]
    if kind == "FIXED_LEN_BYTE_ARRAY":
        plain = s((cap // 128, 128 * width), jnp.uint8)
        table = s((ndict // 128, 128 * width), jnp.uint8)
    else:
        word = jnp.int32 if width == 4 else jnp.int64
        plain, table = s((cap,), word), s((ndict,), word)
    fn = functools.partial(
        pd.pq_fixed, kind=kind, width=width, dict_len=ndict, dtype=dtype,
        cap=cap, merge=merge, nulls=False, dense_idx=True, dense_seg=True,
        dense_def=True)
    _compile(fn, plain if merge else None, table,
             s((2 * cap,), jnp.int32), pieces, pieces, None, None,
             s((), jnp.int32))
