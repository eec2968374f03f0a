"""The TPC-H lake: the lake and its monthly query logs, re-plans through
``PlacementEngine.run``, and the check against ``tpch_lake_ref``.

The lake (``data_seed`` of the configuration) and the pool of monthly logs
(``log_seed`` of the mix) are the same for every run: their shapes set
which programs the entropy features compile, and their sizes the work of
a plan, so a run's seed that drew them would change both. The run's seed
sets the order in which the pool is replayed, and with it the log whose
queries COMPREDICT is fitted on, and so the predicted ratios and times
that the plans are priced on.

The lake is TPC-H's eight tables with every column of the spec at the
configured scale factor, stored as files of ``rows_per_file`` rows; the
fact tables are clustered by date, as a lake ingests time-ordered
events. A month's log instantiates each of the repository's 22
single-table selection patterns (ranges and equalities over the TPC-H
schema, after the paper's TPC-H evaluation; not TPC-H's own queries)
``queries_per_template`` times; the files each query touches make its
family, and each logged query is read ``rho_per_query`` times over the
billing horizon. The generator is kept here so that the program cannot
change the traffic it is measured on.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import sys
from typing import Dict, List, Tuple

import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parents[1] / "src"), str(_HERE.parent)]

import cost_ref  # noqa: E402
from pricing import cost_table  # noqa: E402
from repro.core.compredict import (MODELS, CompressionPredictor,  # noqa: E402
                                   extract_features)
from repro.core.costs import Weights  # noqa: E402
from repro.core.datapart import FileSizes, Partition  # noqa: E402
from repro.core.engine import PlacementEngine, ScopeConfig  # noqa: E402
from repro.data.tables import Table  # noqa: E402


def _load(name: str):
    spec = importlib.util.spec_from_file_location(name, _HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("tpch_lake_ref")

# ------------------------------------------------------------- generation
# TPC-H v3 clause 4.2: the value lists and the grammar of the text columns
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
                     "HOUSEHOLD"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"])
SHIPMODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                      "FOB"])
INSTRUCTIONS = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                         "TAKE BACK RETURN"])
NATIONS = np.array([
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA", "FRANCE",
    "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN", "JORDAN",
    "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES"])
NATION_REGION = np.array([0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0,
                          1, 2, 3, 4, 2, 3, 3, 1])
REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"])
TYPES = np.array([f"{a} {b} {c}"
                  for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                            "PROMO")
                  for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                            "BRUSHED")
                  for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")])
CONTAINERS = np.array([f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
                       for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK",
                                 "CAN", "DRUM")])
COLORS = np.array((
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split())
WORDS = {
    "noun": ("foxes ideas theodolites pinto_beans instructions dependencies "
             "excuses platelets asymptotes courts dolphins multipliers "
             "sauternes warthogs frets dinos attainments somas Tiresias' "
             "patterns forges braids hockey_players frays warhorses dugouts "
             "notornis epitaphs pearls tithes waters orbits gifts sheaves "
             "depths sentiments decoys realms pains grouches escapades"),
    "verb": ("sleep wake are cajole haggle nag use boost affix detect "
             "integrate maintain nod was lose sublate solve thrash promise "
             "engage hinder print x-ray breach eat grow impress mold poach "
             "serve run dazzle snooze doze unwind kindle play hang believe "
             "doubt"),
    "adjective": ("furious sly careful blithe quick fluffy slow quiet "
                  "ruthless thin close dogged daring brave stealthy "
                  "permanent enticing idle busy regular final ironic even "
                  "bold silent"),
    "adverb": ("sometimes always never furiously slyly carefully blithely "
               "quickly fluffily slowly quietly ruthlessly thinly closely "
               "doggedly daringly bravely stealthily permanently enticingly "
               "idly busily regularly finally ironically evenly boldly "
               "silently"),
    "preposition": ("about above according_to across after against along "
                    "alongside_of among around at atop before behind "
                    "beneath beside besides between beyond by despite "
                    "during except for from in_place_of inside instead_of "
                    "into near of on outside over past since through "
                    "throughout to toward under until up upon without with "
                    "within"),
    "auxiliary": ("do may might shall will would can could should ought_to "
                  "must will_have_to shall_have_to could_have_to "
                  "should_have_to must_have_to need_to try_to"),
    "terminator": ". ; : ? ! --",
}
WORDS = {k: [w.replace("_", " ") for w in v.split()]
         for k, v in WORDS.items()}
START = np.datetime64("1992-01-01")       # STARTDATE
END_DAYS = 2556                            # ENDDATE 1998-12-31
CURRENT_DAYS = 1263                        # CURRENTDATE 1995-06-17


def text_pool(rng: np.random.Generator, nbytes: int,
              sentences: int = 8192) -> np.ndarray:
    """The spec's pseudo-text, as bytes: ``sentences`` sentences of the
    grammar of the spec's clause 4.2.2 (noun phrase, verb phrase, an
    optional prepositional or noun phrase, a terminator), strung in a
    random order until the pool holds ``nbytes``."""
    pick = lambda kind: WORDS[kind][rng.integers(len(WORDS[kind]))]

    def noun_phrase():
        form = rng.integers(4)
        if form == 0:
            return pick("noun")
        if form == 1:
            return f"{pick('adjective')} {pick('noun')}"
        if form == 2:
            return f"{pick('adjective')}, {pick('adjective')} {pick('noun')}"
        return f"{pick('adverb')} {pick('adjective')} {pick('noun')}"

    def verb_phrase():
        form = rng.integers(4)
        verb = pick("verb")
        if form & 1:
            verb = f"{pick('auxiliary')} {verb}"
        return f"{verb} {pick('adverb')}" if form & 2 else verb

    prep = lambda: f"{pick('preposition')} the {noun_phrase()}"
    made = []
    for _ in range(sentences):
        form = rng.integers(5)
        words = [noun_phrase(), verb_phrase()] if form < 3 else \
            [noun_phrase(), prep(), verb_phrase()]
        if form == 1 or form == 4:
            words.append(prep())
        elif form in (2, 3):
            words.append(noun_phrase())
        made.append((" ".join(words) + pick("terminator")).encode())
    mean = sum(map(len, made)) / len(made) + 1
    order = rng.integers(0, sentences, int(nbytes / mean) + 1)
    return np.frombuffer(b" ".join([made[i] for i in order]), np.uint8)


def texts(pool: np.ndarray, rng, n: int, lo: int, hi: int) -> np.ndarray:
    """TEXT[lo, hi]: a substring of the pool at a random offset, of a
    length uniform in [lo, hi]."""
    off = rng.integers(0, len(pool) - hi, n)
    length = rng.integers(lo, hi + 1, n)
    j = np.arange(hi)
    chars = np.where(j[None, :] < length[:, None],
                     pool[off[:, None] + j[None, :]], 0).astype(np.uint8)
    return chars.view(f"S{hi}").ravel().astype(f"U{hi}")


ALPHANUM = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyz"
                         b"ABCDEFGHIJKLMNOPQRSTUVWXYZ, ", np.uint8)


def v_strings(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """V-string[lo, hi]: random characters of a 64-letter alphabet, of a
    length uniform in [lo, hi]."""
    length = rng.integers(lo, hi + 1, n)
    chars = ALPHANUM[rng.integers(0, len(ALPHANUM), (n, hi))]
    chars[np.arange(hi)[None, :] >= length[:, None]] = 0
    return chars.view(f"S{hi}").ravel().astype(f"U{hi}")


def phones(rng, nation: np.ndarray) -> np.ndarray:
    """CC-LLL-LLL-LLLL with country code nation + 10."""
    n = len(nation)
    parts = [np.char.mod("%d", nation + 10),
             np.char.mod("%d", rng.integers(100, 1000, n)),
             np.char.mod("%d", rng.integers(100, 1000, n)),
             np.char.mod("%d", rng.integers(1000, 10000, n))]
    out = parts[0]
    for p in parts[1:]:
        out = np.char.add(np.char.add(out, "-"), p)
    return out


def iso(days: np.ndarray) -> np.ndarray:
    """Days after STARTDATE as YYYY-MM-DD."""
    return np.datetime_as_string(
        START + np.asarray(days).astype("timedelta64[D]"),
        unit="D").astype("U10")


def join_words(words: np.ndarray) -> np.ndarray:
    out = words[:, 0]
    for k in range(1, words.shape[1]):
        out = np.char.add(np.char.add(out, " "), words[:, k])
    return out


def make_lake(sf: float, rng: np.random.Generator) -> Dict[str, Table]:
    """TPC-H's eight tables with every column of clause 1.4 at the spec's
    cardinalities for scale factor ``sf``: keys, fixed and variable text
    of the stated lengths, the pseudo-text comments, the dependent columns
    (prices, dates, flags) as clause 4.2.3 derives them. Dates are
    YYYY-MM-DD strings, decimals floats of two places."""
    pool = text_pool(rng, int(rng.integers(3, 5)) << 20)
    n_part = max(int(200_000 * sf), 1)
    n_supp = max(int(10_000 * sf), 1)
    n_cust = max(int(150_000 * sf), 1)
    n_ord = max(int(1_500_000 * sf), 1)
    money = lambda lo, hi, n: np.round(rng.uniform(lo, hi, n), 2)

    region = Table("region", {
        "r_regionkey": np.arange(len(REGIONS)), "r_name": REGIONS.copy(),
        "r_comment": texts(pool, rng, len(REGIONS), 31, 115)})
    nation = Table("nation", {
        "n_nationkey": np.arange(len(NATIONS)), "n_name": NATIONS.copy(),
        "n_regionkey": NATION_REGION.copy(),
        "n_comment": texts(pool, rng, len(NATIONS), 31, 114)})
    s_nation = rng.integers(0, len(NATIONS), n_supp)
    supplier = Table("supplier", {
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_name": np.char.mod("Supplier#%09d", np.arange(1, n_supp + 1)),
        "s_address": v_strings(rng, n_supp, 10, 40),
        "s_nationkey": s_nation,
        "s_phone": phones(rng, s_nation),
        "s_acctbal": money(-999.99, 9999.99, n_supp),
        "s_comment": texts(pool, rng, n_supp, 25, 100)})
    pkey = np.arange(1, n_part + 1)
    retail = (90000 + (pkey // 10) % 20001 + 100 * (pkey % 1000)) / 100.0
    mfgr = rng.integers(1, 6, n_part)
    part = Table("part", {
        "p_partkey": pkey,
        "p_name": join_words(COLORS[np.argsort(
            rng.random((n_part, len(COLORS))), axis=1)[:, :5]]),
        "p_mfgr": np.char.mod("Manufacturer#%d", mfgr),
        "p_brand": np.char.mod("Brand#%d", mfgr * 10
                               + rng.integers(1, 6, n_part)),
        "p_type": TYPES[rng.integers(0, len(TYPES), n_part)],
        "p_size": rng.integers(1, 51, n_part),
        "p_container": CONTAINERS[rng.integers(0, len(CONTAINERS), n_part)],
        "p_retailprice": retail,
        "p_comment": texts(pool, rng, n_part, 5, 22)})
    ps_part = np.repeat(pkey, 4)
    ps_i = np.tile(np.arange(4), n_part)
    supp_of = lambda p, i: (p + i * (n_supp // 4 + (p - 1) // n_supp)) \
        % n_supp + 1
    partsupp = Table("partsupp", {
        "ps_partkey": ps_part,
        "ps_suppkey": supp_of(ps_part, ps_i),
        "ps_availqty": rng.integers(1, 10000, len(ps_part)),
        "ps_supplycost": money(1.0, 1000.0, len(ps_part)),
        "ps_comment": texts(pool, rng, len(ps_part), 49, 198)})
    c_nation = rng.integers(0, len(NATIONS), n_cust)
    customer = Table("customer", {
        "c_custkey": np.arange(1, n_cust + 1),
        "c_name": np.char.mod("Customer#%09d", np.arange(1, n_cust + 1)),
        "c_address": v_strings(rng, n_cust, 10, 40),
        "c_nationkey": c_nation,
        "c_phone": phones(rng, c_nation),
        "c_acctbal": money(-999.99, 9999.99, n_cust),
        "c_mktsegment": SEGMENTS[rng.integers(0, len(SEGMENTS), n_cust)],
        "c_comment": texts(pool, rng, n_cust, 29, 116)})

    # orders and their 1-7 line items
    o_date = rng.integers(0, END_DAYS - 151 + 1, n_ord)
    lines = rng.integers(1, 8, n_ord)
    li_o = np.repeat(np.arange(n_ord), lines)
    n_li = len(li_o)
    first = np.repeat(np.cumsum(lines) - lines, lines)
    l_part = rng.integers(1, n_part + 1, n_li)
    qty = rng.integers(1, 51, n_li).astype(np.float64)
    price = np.round(qty * retail[l_part - 1], 2)
    disc = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    ship = o_date[li_o] + rng.integers(1, 122, n_li)
    commit = o_date[li_o] + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    returned = np.where(rng.integers(0, 2, n_li) == 0, "R", "A")
    status = np.where(ship > CURRENT_DAYS, "O", "F")
    lineitem = Table("lineitem", {
        "l_orderkey": (li_o // 8) * 32 + li_o % 8 + 1,
        "l_partkey": l_part,
        "l_suppkey": supp_of(l_part, rng.integers(0, 4, n_li)),
        "l_linenumber": np.arange(n_li) - first + 1,
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": np.where(receipt <= CURRENT_DAYS, returned, "N"),
        "l_linestatus": status,
        "l_shipdate": iso(ship),
        "l_commitdate": iso(commit),
        "l_receiptdate": iso(receipt),
        "l_shipinstruct": INSTRUCTIONS[rng.integers(0, 4, n_li)],
        "l_shipmode": SHIPMODES[rng.integers(0, len(SHIPMODES), n_li)],
        "l_comment": texts(pool, rng, n_li, 10, 43)})
    n_open = np.bincount(li_o, weights=status == "O", minlength=n_ord)
    o_status = np.where(n_open == lines, "O",
                        np.where(n_open == 0, "F", "P"))
    # custkeys not divisible by three, as the spec leaves a third of the
    # customers without orders
    cands = np.arange(1, n_cust + 1)
    cands = cands[cands % 3 != 0] if n_cust >= 3 else cands
    orders = Table("orders", {
        "o_orderkey": (np.arange(n_ord) // 8) * 32 + np.arange(n_ord) % 8 + 1,
        "o_custkey": cands[rng.integers(0, len(cands), n_ord)],
        "o_orderstatus": o_status,
        "o_totalprice": np.round(np.bincount(
            li_o, weights=price * (1 + tax) * (1 - disc), minlength=n_ord),
            2),
        "o_orderdate": iso(o_date),
        "o_orderpriority": PRIORITIES[rng.integers(0, len(PRIORITIES),
                                                   n_ord)],
        "o_clerk": np.char.mod("Clerk#%09d", rng.integers(
            1, max(int(1000 * sf), 1) + 1, n_ord)),
        "o_shippriority": np.zeros(n_ord, np.int64),
        "o_comment": texts(pool, rng, n_ord, 19, 78)})
    # a lake ingests time-ordered events: the fact tables are clustered by
    # date, so a date range touches a run of files
    tables = [region, nation, supplier, part, partsupp, customer,
              orders.sort_by("o_orderdate"), lineitem.sort_by("l_shipdate")]
    return {t.name: t for t in tables}


def templates():
    """The 22 single-table selection patterns of the repository's TPC-H
    workload (ranges and equalities on one table each; not TPC-H's 22
    queries, which join): ``(table, predicate(table, rng))``. Date bounds
    are days after STARTDATE."""
    def date_range(col, lo, hi):
        return lambda t, rng: ((t.columns[col] >= iso(lo + rng.integers(
            0, 200))) & (t.columns[col] < iso(hi + rng.integers(0, 200))))

    def eq_choice(col, values):
        return lambda t, rng: t.columns[col] == values[
            rng.integers(0, len(values))]

    def num_range(col, lo, hi, width):
        def f(t, rng):
            a = rng.uniform(lo, hi - width)
            return (t.columns[col] >= a) & (t.columns[col] < a + width)
        return f

    out = [("lineitem", date_range("l_shipdate", 360 * k, 360 * (k + 1)))
           for k in range(6)]
    out += [("lineitem", eq_choice("l_shipmode", SHIPMODES)),
            ("lineitem", eq_choice("l_returnflag", np.array(["A", "N",
                                                             "R"]))),
            ("lineitem", num_range("l_quantity", 1, 50, 5)),
            ("lineitem", num_range("l_extendedprice", 900, 105000, 9000))]
    out += [("orders", date_range("o_orderdate", 500 * k, 500 * (k + 1)))
            for k in range(4)]
    out += [("orders", eq_choice("o_orderpriority", PRIORITIES)),
            ("orders", num_range("o_totalprice", 1000, 400000, 40000)),
            ("customer", eq_choice("c_mktsegment", SEGMENTS)),
            ("customer", num_range("c_acctbal", -999, 9999, 1500)),
            ("part", eq_choice("p_type", TYPES[:30])),
            ("part", num_range("p_size", 1, 50, 8)),
            ("partsupp", num_range("ps_supplycost", 1, 1000, 120)),
            ("supplier", num_range("s_acctbal", -999, 9999, 1800))]
    return out


def files_of(lake: Dict[str, Table], rows_per_file: int
             ) -> Dict[str, List[Tuple[str, np.ndarray]]]:
    """table -> [(file id, row indices)]: contiguous row chunks."""
    return {name: [(f"{name}/{i:05d}",
                    np.arange(lo, min(lo + rows_per_file, t.num_rows)))
                   for i, lo in enumerate(range(0, t.num_rows,
                                                rows_per_file))]
            for name, t in lake.items()}


def month_log(lake, files, per_template: int, rng) -> list:
    """One month's queries as (table, matched rows, files touched)."""
    log = []
    for table_name, pred in templates():
        t = lake[table_name]
        for _ in range(per_template):
            mask = pred(t, rng)
            touched = tuple(fid for fid, idx in files[table_name]
                            if mask[idx].any())
            log.append((table_name, np.nonzero(mask)[0], touched))
    return log


def families(log, rho_per_query: float) -> List[ref.Family]:
    """Queries that touch the same files make one family; reads add up."""
    fam: Dict[frozenset, float] = {}
    for _, _, touched in log:
        if touched:
            key = frozenset(touched)
            fam[key] = fam.get(key, 0.0) + rho_per_query
    return list(fam.items())


# ------------------------------------------------------------------- cell
class KeptFeatures(CompressionPredictor):
    """Keeps the feature matrix of CompressStage's own call, so the check
    reads what the engine computed, and fits on labels given to it."""

    def features(self, tables, layout, **kw):
        self.X = super().features(tables, layout, **kw)
        return self.X

    def fit_labels(self, samples, labels: dict, layout: str):
        """``fit`` with the labels measured by the benchmark (``labels``:
        scheme -> (ratios, decompression s/GB) of ``samples``): the
        program's features and models, one per scheme and target."""
        X = np.stack([extract_features(t, layout, self.feature_kind)
                      for t in samples])
        for scheme, targets in labels.items():
            for target, y in zip(("ratio", "dspeed"), targets):
                self.models[(scheme, layout, target)] = \
                    MODELS[self.model_name]().fit(X, y)
        return self


@dataclasses.dataclass
class Answer:
    """What one re-plan produced."""

    parts: List[ref.Family]     # partitions in the plan's order
    X: np.ndarray               # (N, F) CompressStage's features
    R: np.ndarray               # (N, K)
    D: np.ndarray               # (N, K) seconds
    tier: np.ndarray
    scheme: np.ndarray
    objective: float            # the plan's cost as the program reports it
    bill: np.ndarray            # storage, read, decomp, total cents


class LakeCell:
    def __init__(self, config: dict, mix: dict, seed: int, rec):
        self.config, self.rec = config, rec
        self.lake = make_lake(float(config["scale_factor"]),
                              np.random.default_rng(int(config["data_seed"])))
        files = files_of(self.lake, int(config["rows_per_file"]))
        self.file_rows = {fid: (self.lake[name], idx)
                          for name, fl in files.items() for fid, idx in fl}
        self.sizes = {fid: float(ref.col_layout_bytes(
            {k: v[idx] for k, v in self.lake[name].columns.items()}))
            for name, fl in files.items() for fid, idx in fl}
        logs = [month_log(self.lake, files, int(mix["queries_per_template"]),
                          np.random.default_rng([int(mix["log_seed"]),
                                                 1 + m]))
                for m in range(int(mix["months"]))]
        self.families = [families(log, float(mix["rho_per_query"]))
                         for log in logs]
        fs = FileSizes(self.sizes)
        self.pool = [int(j) for j in
                     np.random.default_rng(seed).permutation(len(logs))]
        self.requests = [[Partition(f, r, fs) for f, r in fam]
                         for fam in self.families]
        # the overlap kernel's problem: families, distinct files and
        # family-file memberships
        self.overlap_shape = [(len(fam), len(set().union(*(f for f, _ in
                                                           fam))),
                               sum(len(f) for f, _ in fam))
                              for fam in self.families]

        # COMPREDICT learns from the queries of the first log served; the
        # benchmark measures the labels, so the reference fits on the same
        cp = config["compredict"]
        rows = [(t, r[:cp["sample_rows"]]) for t, r, _ in logs[self.pool[0]]
                if len(r)]
        step = max(len(rows) // cp["fit_samples"], 1)
        self.samples = [{k: v[r] for k, v in self.lake[t].columns.items()}
                        for t, r in rows[::step][:cp["fit_samples"]]]
        self.samples = [c for c in self.samples
                        if ref.col_layout_bytes(c) >= 64]
        schemes = [s for s in config["schemes"] if s != "none"]
        self.labels = ref.labels(self.samples, schemes)
        self.predictor = KeptFeatures(model_name=cp["model"]).fit_labels(
            [Table("sample", c) for c in self.samples], self.labels,
            config["layout"])
        backend = config["kernel_backend"]
        w, g = config["weights"], config["g_part"]
        self.table = cost_table(config["pricing"])
        cfg = ScopeConfig(
            schemes=tuple(config["schemes"]), layout=config["layout"],
            months=float(config["months"]), weights=Weights(**w),
            tier_whitelist=tuple(config["tier_whitelist"]),
            s_thresh_mult=g["s_thresh_mult"], rho_c=g["rho_c"],
            rho_c_abs=g["rho_c_abs"], predictor=self.predictor,
            partition_backend=backend, feature_backend=backend)
        self.engine = PlacementEngine(self.table, cfg)
        for stage, name in (("partition", "PartitionStage"),
                            ("compress", "CompressStage"),
                            ("assign", "AssignStage"),
                            ("billing", "BillingStage")):
            setattr(self.engine, stage,
                    rec.wrap(name, getattr(self.engine, stage)))

    def serve(self, m: int) -> Answer:
        """Re-plan the lake from log ``m``."""
        self.rec.count("overlap_shape", self.overlap_shape[m])
        self.rec.count("pool", m)
        plan = self.engine.run(self.requests[m], self.file_rows)
        p, a, r = plan.problem, plan.assignment, plan.report
        return Answer(
            parts=[(q.files, q.rho) for q in p.partitions],
            X=self.predictor.X, R=p.R, D=p.D,
            tier=np.asarray(a.tier), scheme=np.asarray(a.scheme),
            objective=a.cost,
            bill=np.array([r.storage_cents, r.read_cents, r.decomp_cents,
                           r.total_cents]))

    def units(self, ans: Answer) -> dict:
        return {"plans": 1}

    # ---------------------------------------------------------------- check
    def model(self) -> ref.Ridge:
        """The reference's COMPREDICT: its own features of the samples and
        the benchmark's labels, fitted as the configuration states."""
        if not hasattr(self, "_model"):
            cp = self.config["compredict"]["model_spec"]
            X = np.stack([ref.describe(c)[1] for c in self.samples])
            self._model = ref.Ridge(X, self.labels, float(cp["alpha"]))
        return self._model

    def reference(self, m: int, dtype=np.float64) -> dict:
        """The plain pipeline's partitions for log ``m``, with per
        partition its columns' size, features, predicted ratios and
        decompression seconds, and (values, distinct) per class."""
        g = self.config["g_part"]
        parts = ref.g_part(self.families[m], self.sizes, g["s_thresh_mult"],
                           g["rho_c"], g["rho_c_abs"])
        out = {}
        for files, rho in parts:
            size, x, shape = ref.describe(
                ref.partition_columns(files, self.file_rows), dtype=dtype)
            r, d = self.model().predict(x, self.config["schemes"])
            out[files] = dict(rho=rho, size=size, X=x, R=r, D=d * size / 1e9,
                              shape=shape)
        return out

    def plan_cost(self, ans: Answer, spans) -> tuple:
        """(cost tensor over the whitelisted tiers, reads) of the plan's
        partitions, priced on the reference's spans and the answer's
        ratios and decompression times."""
        rho = np.array([r for _, r in ans.parts])
        cost = cost_ref.cost_tensor(spans, rho, ans.R, ans.D,
                                    self.config["pricing"],
                                    self.config["weights"],
                                    self.config["months"])
        allowed = np.zeros(cost.shape[1], bool)
        allowed[list(self.config["tier_whitelist"])] = True
        return np.where(allowed[None, :, None], cost, np.inf), rho

    def readings(self, ans: Answer, want: dict) -> dict:
        inf = float("inf")
        got = {f: r for f, r in ans.parts}
        differ = (len(set(got.items()) ^ {(f, v["rho"])
                                          for f, v in want.items()}))
        r = {"partitions_differ": float(differ)}
        if differ or ans.X.shape[0] != len(ans.parts):
            return dict(r, feature_err=inf, predict_err=inf, plan_gap=inf,
                        bill_gap=inf)
        X_ref = np.stack([want[f]["X"] for f, _ in ans.parts])
        r["feature_err"] = float((np.abs(ans.X - X_ref)
                                  / np.maximum(np.abs(X_ref), 1.0)).max())
        # the predictions from the features, against the reference's own
        # model on its own features: per scheme and target, relative to
        # the larger of the value and the mean magnitude of the column
        err = 0.0
        for key in ("R", "D"):
            want_m = np.stack([want[f][key] for f, _ in ans.parts])
            scale = np.maximum(np.abs(want_m),
                               np.abs(want_m).mean(0, keepdims=True))
            gap = np.abs(getattr(ans, key) - want_m)
            err = max(err, float(np.where(gap > 0, gap / np.maximum(
                scale, 1e-300), 0.0).max()))
        r["predict_err"] = err
        # the plan and the bill, priced on the answer's predictions, which
        # predict_err has held to the reference's
        spans = np.array([want[f]["size"] / 1e9 for f, _ in ans.parts])
        cost, rho = self.plan_cost(ans, spans)
        best = cost_ref.plan_cost(cost, *cost_ref.argmin_plan(cost))
        got = cost_ref.plan_cost(cost, ans.tier, ans.scheme)
        # the reported objective is the least there is, and is what the
        # chosen cells cost
        r["plan_gap"] = max(abs(ans.objective - best),
                            abs(got - best)) / abs(best)
        b = cost_ref.bill(spans, rho, ans.R, ans.D, ans.tier, ans.scheme,
                          self.config["pricing"], self.config["months"])
        want_bill = np.array([b["storage"], b["read"], b["decomp"],
                              b["total"]])
        r["bill_gap"] = float((np.abs(ans.bill - want_bill)
                               / np.maximum(np.abs(want_bill), 1e-12)).max())
        return r

    def control(self, j: int, served: Answer) -> Answer:
        """The reference in the program's place, one precision down:
        features in bfloat16 (the program's are float32) and the
        reference's model on them, the plan from a bfloat16 argmin (the
        program's device argmin is float32), its objective and bill in
        float32 (the program's are float64). ``served`` is not read."""
        import ml_dtypes
        want = self.reference(self.pool[j], dtype=ml_dtypes.bfloat16)
        parts = list(want.items())
        spans = np.array([v["size"] / 1e9 for _, v in parts])
        ctl = Answer([(f, v["rho"]) for f, v in parts],
                     np.stack([v["X"] for _, v in parts]),
                     np.stack([v["R"] for _, v in parts]),
                     np.stack([v["D"] for _, v in parts]),
                     None, None, None, None)
        cost, rho = self.plan_cost(ctl, spans)
        ctl.tier, ctl.scheme = cost_ref.argmin_plan(
            cost.astype(ml_dtypes.bfloat16))
        ctl.objective = cost_ref.plan_cost(cost.astype(np.float32), ctl.tier,
                                           ctl.scheme)
        b = cost_ref.bill(spans, rho, ctl.R, ctl.D, ctl.tier, ctl.scheme,
                          self.config["pricing"], self.config["months"],
                          dtype=np.float32)
        ctl.bill = np.array([b["storage"], b["read"], b["decomp"],
                             b["total"]])
        return ctl

    def check(self, answers) -> list:
        """``[(name, worst reading, limit)]`` over every answer."""
        for name in ("engine", "predictor"):    # the program's state goes
            self.__dict__.pop(name, None)
        # answers are keyed by pool position; the reference by log
        wants = {m: self.reference(m)
                 for m in sorted({self.pool[j] for j, _ in answers})}
        self.shapes = {m: [w["shape"] for w in want.values()]
                       for m, want in wants.items()}
        worst: Dict[str, float] = {}
        for j, ans in answers:
            for k, v in self.readings(ans, wants[self.pool[j]]).items():
                worst[k] = max(worst.get(k, 0.0), v)
        limits = self.config["limits"]
        return [(k, worst[k], limits[k]) for k in sorted(worst)]


def build(config: dict, mix: dict, seed: int, rec) -> LakeCell:
    return LakeCell(config, mix, seed, rec)
