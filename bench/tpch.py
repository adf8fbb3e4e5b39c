"""TPC-H data, queries and query templates.

After the TPC-H Standard Specification (Transaction Processing Performance
Council, revision 3.0.x) and its generator ``dbgen``: the eight tables with
the columns, value domains and sizes of clause 4.2.3, and the 22 queries of
clause 2.4 word for word, with the validation parameters each query's
definition lists.  Every table is drawn from the seed.

Numbers follow ``dbgen``: keys as it numbers them (orders' keys sparse, 8
used of every 32), prices, costs and balances in whole cents, discounts and
taxes in whole percents, dates from 1992-01-01 to 1998-12-31 with orders to
151 days before the end, ship, commit and receipt dates and the return flag
and line status derived from them as clause 4.2.3 says.  DECIMAL columns
hold the cents over 100 as float64 (``DOUBLE``), DATE columns ISO strings
(``yyyy-mm-dd``), which compare as dates do.  Text columns come from
``dbgen``'s word lists, drawn from a pool of sentences made from the seed.
What the specification leaves to the generator, and where this one departs
from ``dbgen``, is listed under ``assumed`` in the configuration's file.

A configuration names this module as its ``"dataset"``; the harness asks it
for ``generate``, ``warm_copy``, ``warm_bound``, ``load``, ``PUBLISHED``,
``TEMPLATES``, ``draw_params``, ``KEYS`` and ``reference_sql``.
"""
from __future__ import annotations

import re

import numpy as np

# clause 4.2.3: (nation, region key) by nation key, and the regions
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
)
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()
TYPES = (("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"),
         ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"),
         ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
CONTAINERS = (("SM", "LG", "MED", "JUMBO", "WRAP"),
              ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"))
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
SHIPMODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# dbgen's grammar words (dists.dss), for the text columns
NOUNS = (
    "foxes ideas theodolites pinto_beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias' patterns forges braids "
    "hockey_players frays warhorses dugouts notornis epitaphs pearls tithes "
    "waters orbits gifts sheaves depths sentiments decoys realms pains "
    "grouches escapades packages requests accounts deposits").replace(
        "_", " ").split(" ")
VERBS = (
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder print "
    "x-ray breach eat grow impress mold poach serve run dazzle snooze doze "
    "unwind kindle play hang believe doubt").split()
ADJECTIVES = (
    "special pending unusual express furious sly careful blithe quick fluffy "
    "slow quiet ruthless thin close dogged daring brave stealthy permanent "
    "enticing idle busy regular final ironic even bold silent").split()
ADVERBS = (
    "sometimes always never furiously slyly carefully blithely quickly "
    "fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently").split()
PREPOSITIONS = (
    "about above according_to across after against along alongside_of "
    "among around at atop before behind beneath beside besides between "
    "beyond by despite during except for from in_place_of inside instead_of "
    "into near of on outside over past since through throughout to toward "
    "under until up upon without with within").replace("_", " ").split(" ")
TERMINATORS = (".", ";", ":", "?", "!", "--")

SCALE_ROWS = {"part": 200_000, "supplier": 10_000, "customer": 150_000,
              "orders": 1_500_000}  # at scale factor 1 (clause 4.2.5)
START = np.datetime64("1992-01-01")
END = np.datetime64("1998-12-31")
CURRENT = np.datetime64("1995-06-17")  # clause 4.2.3's CURRENTDATE
ORDER_DAYS = int((END - START).astype(int)) - 151 + 1
TEXT_POOL = 4096  # distinct texts drawn a text column's values from
REHEARSAL_SUPPLIERS = 200  # a rehearsal's floor on suppliers, so Q11's
# and Q16's shapes keep rows


def _pool(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` texts of ``lo`` to ``hi`` characters, sentences of dbgen's
    grammar cut at a random length."""
    words = (ADJECTIVES, NOUNS, VERBS, ADVERBS, PREPOSITIONS, ADJECTIVES,
             NOUNS)
    out = []
    for length in rng.integers(lo, hi + 1, n).tolist():
        text = ""
        while len(text) < length:
            picks = [w[int(rng.integers(0, len(w)))] for w in words]
            text += ("{} {} {} {} {} the {} {}".format(*picks)
                     + TERMINATORS[int(rng.integers(0, 6))] + " ")
        out.append(text[:length].rstrip() or text[:length])
    return np.array(out)


def _text(rng, n: int, lo: int, hi: int) -> np.ndarray:
    pool = _pool(rng, min(n, TEXT_POOL), lo, hi)
    return pool[rng.integers(0, len(pool), n)]


def _vstring(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """clause 4.2.2.7's random v-strings: ``lo`` to ``hi`` characters of
    letters, digits, spaces and commas, from a pool."""
    alpha = np.array(list("abcdefghijklmnopqrstuvwxyz"
                          "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,"))
    m = min(n, TEXT_POOL)
    chars = alpha[rng.integers(0, len(alpha), (m, hi))]
    lens = rng.integers(lo, hi + 1, m)
    pool = np.array(["".join(c[:k]) for c, k in zip(chars, lens)])
    return pool[rng.integers(0, m, n)]


def _phone(rng, nation: np.ndarray) -> np.ndarray:
    d = rng.integers((100, 100, 1000), (1000, 1000, 10000), (len(nation), 3))
    return np.array([f"{10 + k}-{a}-{b}-{c}"
                     for k, (a, b, c) in zip(nation.tolist(), d.tolist())])


def _money(cents) -> np.ndarray:
    return np.asarray(cents, dtype=np.float64) / 100.0


def _numbered(prefix: str, keys: np.ndarray) -> np.ndarray:
    return np.char.add(prefix, np.char.zfill(keys.astype(str), 9))


def _days(start_offsets: np.ndarray) -> np.ndarray:
    """ISO date strings of day offsets from 1992-01-01."""
    names = np.datetime_as_string(START + np.arange(int(
        (END - START).astype(int)) + 200))
    return names.astype("U10")[start_offsets]


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """clause 4.2.3's P_RETAILPRICE, in cents, from the part key."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def partsupp_suppkey(partkey: np.ndarray, i, n_supp: int) -> np.ndarray:
    """The ``i``-th (0-3) supplier of a part, as clause 4.2.3 numbers it."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def sizes(config: dict, rehearse_rows=None) -> dict:
    """Rows of part, supplier, customer and orders: the scale factor's, or
    in a rehearsal ``rehearse_rows`` lineitem rows' worth (about 4 lines an
    order), every table cut in the same proportion."""
    sf = float(config["scale_factor"])
    if rehearse_rows is not None:
        sf = rehearse_rows / 4.0 / SCALE_ROWS["orders"]
    n = {t: max(int(round(r * sf)), 1) for t, r in SCALE_ROWS.items()}
    if rehearse_rows is not None:
        n["supplier"] = max(n["supplier"], REHEARSAL_SUPPLIERS)
    return n


def generate(seed: int, config: dict, rehearse_rows=None) -> dict:
    """``{table: {column: ndarray}}`` for one seed at the configuration's
    ``scale_factor``; a rehearsal draws about ``rehearse_rows`` lineitem
    rows and cuts the other tables in proportion."""
    n = sizes(config, rehearse_rows)
    n_part, n_supp, n_cust, n_ord = (n["part"], n["supplier"],
                                     n["customer"], n["orders"])
    rng = np.random.default_rng([seed, 0])
    tables = {
        "region": {
            "r_regionkey": np.arange(5),
            "r_name": np.array(REGIONS),
            "r_comment": _text(rng, 5, 31, 115),
        },
        "nation": {
            "n_nationkey": np.arange(25),
            "n_name": np.array([a for a, _ in NATIONS]),
            "n_regionkey": np.array([b for _, b in NATIONS]),
            "n_comment": _text(rng, 25, 31, 114),
        },
    }

    partkey = np.arange(1, n_part + 1)
    colors = np.array(COLORS)
    pick = np.argpartition(rng.random((n_part, len(COLORS))), 5,
                           axis=1)[:, :5]
    name = colors[pick[:, 0]]
    for j in range(1, 5):
        name = np.char.add(np.char.add(name, " "), colors[pick[:, j]])
    mfgr = rng.integers(1, 6, n_part)
    brand = mfgr * 10 + rng.integers(1, 6, n_part)
    types = [np.array(t)[rng.integers(0, len(t), n_part)] for t in TYPES]
    conts = [np.array(t)[rng.integers(0, len(t), n_part)] for t in CONTAINERS]
    tables["part"] = {
        "p_partkey": partkey,
        "p_name": name,
        "p_mfgr": np.char.add("Manufacturer#", mfgr.astype(str)),
        "p_brand": np.char.add("Brand#", brand.astype(str)),
        "p_type": np.char.add(np.char.add(np.char.add(types[0], " "),
                                          np.char.add(types[1], " ")),
                              types[2]),
        "p_size": rng.integers(1, 51, n_part),
        "p_container": np.char.add(np.char.add(conts[0], " "), conts[1]),
        "p_retailprice": _money(retail_cents(partkey)),
        "p_comment": _text(rng, n_part, 5, 22),
    }

    suppkey = np.arange(1, n_supp + 1)
    s_nation = rng.integers(0, 25, n_supp)
    s_comment = _text(rng, n_supp, 25, 100)
    # clause 4.2.3: SF x 5 rows each of complaints and recommendations
    marked = rng.choice(n_supp, 2 * max(n_supp // 2000, 1), replace=False)
    for k, row in enumerate(marked.tolist()):
        s_comment[row] = _customer_remark(
            rng, str(s_comment[row]),
            "Complaints" if k % 2 == 0 else "Recommends")
    tables["supplier"] = {
        "s_suppkey": suppkey,
        "s_name": _numbered("Supplier#", suppkey),
        "s_address": _vstring(rng, n_supp, 10, 40),
        "s_nationkey": s_nation,
        "s_phone": _phone(rng, s_nation),
        "s_acctbal": _money(rng.integers(-99999, 1000000, n_supp)),
        "s_comment": s_comment.astype(f"U{max(101, s_comment.itemsize // 4)}"),
    }

    ps_part = np.repeat(partkey, 4)
    tables["partsupp"] = {
        "ps_partkey": ps_part,
        "ps_suppkey": partsupp_suppkey(ps_part, np.tile(np.arange(4), n_part),
                                       n_supp),
        "ps_availqty": rng.integers(1, 10000, 4 * n_part),
        "ps_supplycost": _money(rng.integers(100, 100001, 4 * n_part)),
        "ps_comment": _text(rng, 4 * n_part, 49, 198),
    }

    custkey = np.arange(1, n_cust + 1)
    c_nation = rng.integers(0, 25, n_cust)
    tables["customer"] = {
        "c_custkey": custkey,
        "c_name": _numbered("Customer#", custkey),
        "c_address": _vstring(rng, n_cust, 10, 40),
        "c_nationkey": c_nation,
        "c_phone": _phone(rng, c_nation),
        "c_acctbal": _money(rng.integers(-99999, 1000000, n_cust)),
        "c_mktsegment": np.array(SEGMENTS)[rng.integers(0, 5, n_cust)],
        "c_comment": _text(rng, n_cust, 29, 116),
    }
    tables["orders"], tables["lineitem"] = _orders(
        np.random.default_rng([seed, 1]), n_ord, n_cust, n_supp, n_part)
    return tables


def _customer_remark(rng, text: str, word: str) -> str:
    """clause 4.2.3's S_COMMENT with ``Customer `` and then ``word`` written
    over it at a random place, the comment's length kept."""
    core = "Customer " + word
    at = int(rng.integers(0, max(len(text) - len(core), 0) + 1))
    return text[:at] + core + text[at + len(core):]


def _orders(rng, n_ord: int, n_cust: int, n_supp: int, n_part: int):
    """Orders and their 1 to 7 lines each, as clause 4.2.3 derives them."""
    idx = np.arange(n_ord)
    orderkey = (idx // 8) * 32 + idx % 8 + 1  # 8 keys used of every 32
    # a customer whose key is a multiple of 3 places no order
    j = rng.integers(0, n_cust - n_cust // 3, n_ord)
    o_cust = 3 * (j // 2) + j % 2 + 1
    o_day = rng.integers(0, ORDER_DAYS, n_ord)
    lines = rng.integers(1, 8, n_ord)
    n = int(lines.sum())
    order = np.repeat(idx, lines)
    first = np.concatenate(([0], np.cumsum(lines)[:-1]))
    linenumber = np.arange(n) - first[order] + 1

    partkey = rng.integers(1, n_part + 1, n)
    suppkey = partsupp_suppkey(partkey, rng.integers(0, 4, n), n_supp)
    quantity = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    ext_cents = quantity * retail_cents(partkey)
    ship = o_day[order] + rng.integers(1, 122, n)
    commit = o_day[order] + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    current = int((CURRENT - START).astype(int))
    returned = receipt <= current
    flag = np.where(returned,
                    np.where(rng.integers(0, 2, n) == 0, "R", "A"), "N")
    status = np.where(ship > current, "O", "F")
    # the order's status: F or O when all its lines are, else P
    n_open = np.bincount(order, weights=(status == "O"), minlength=n_ord)
    o_status = np.where(n_open == lines, "O",
                        np.where(n_open == 0, "F", "P"))
    charge = np.rint(ext_cents * (100 - discount) * (100 + tax) / 10000.0)
    total = np.bincount(order, weights=charge, minlength=n_ord)

    orders = {
        "o_orderkey": orderkey,
        "o_custkey": o_cust,
        "o_orderstatus": o_status,
        "o_totalprice": _money(total),
        "o_orderdate": _days(o_day),
        "o_orderpriority": np.array(PRIORITIES)[rng.integers(0, 5, n_ord)],
        "o_clerk": _numbered("Clerk#", rng.integers(
            1, max(n_ord // 1500, 1) + 1, n_ord)),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _text(rng, n_ord, 19, 78),
    }
    lineitem = {
        "l_orderkey": orderkey[order],
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": _money(ext_cents),
        "l_discount": _money(discount),
        "l_tax": _money(tax),
        "l_returnflag": flag,
        "l_linestatus": status,
        "l_shipdate": _days(ship),
        "l_commitdate": _days(commit),
        "l_receiptdate": _days(receipt),
        "l_shipinstruct": np.array(INSTRUCTIONS)[rng.integers(0, 4, n)],
        "l_shipmode": np.array(SHIPMODES)[rng.integers(0, 7, n)],
        "l_comment": _text(rng, n, 10, 43),
    }
    return orders, lineitem


def _rows(cols: dict) -> int:
    return len(next(iter(cols.values())))


def warm_copy(tables: dict, stripe_rows: int) -> dict:
    """The tables the warm-up runs on: nation, region and supplier whole,
    and of part, customer and orders a first full stripe and a last stripe
    as long as the real one's, with the partsupp rows of those parts and
    the lines of those orders."""
    out = {t: tables[t] for t in ("nation", "region", "supplier")}
    for t in ("part", "customer", "orders"):
        rows = _rows(tables[t])
        keep = min(rows, stripe_rows + (rows % stripe_rows or stripe_rows))
        out[t] = {c: v[:keep] for c, v in tables[t].items()}
    parts = int(out["part"]["p_partkey"][-1])
    last = int(out["orders"]["o_orderkey"][-1])
    for t, col, bound in (("partsupp", "ps_partkey", parts),
                          ("lineitem", "l_orderkey", last)):
        keep = int(np.searchsorted(tables[t][col], bound, side="right"))
        out[t] = {c: v[:keep] for c, v in tables[t].items()}
    return out


def warm_bound(tables: dict) -> int:
    """The most keys any build side can hold: orders' rows, the largest
    table a plan builds a dictionary or bloom filter from (lineitem and
    partsupp join on keys of orders, part and supplier)."""
    return _rows(tables["orders"])


# each table's single-column key, which the reference declares its primary
# key; lineitem's and partsupp's keys span two columns and get none
KEYS = {"region": "r_regionkey", "nation": "n_nationkey",
        "part": "p_partkey", "supplier": "s_suppkey",
        "customer": "c_custkey", "orders": "o_orderkey"}

_INTERVAL = re.compile(
    r"date\s+'(\d{4}-\d{2}-\d{2})'\s*([+-])\s*interval\s+'(\d+)'\s+"
    r"(day|month|year)(?:\s*\(\s*\d+\s*\))?", re.IGNORECASE)
_DATE = re.compile(r"date\s+'(\d{4}-\d{2}-\d{2})'", re.IGNORECASE)
_EXTRACT = re.compile(r"extract\s*\(\s*year\s+from\s+([\w.]+)\s*\)",
                      re.IGNORECASE)
_SUBSTRING = re.compile(
    r"substring\s*\(\s*([\w.]+)\s+from\s+(\d+)\s+for\s+(\d+)\s*\)",
    re.IGNORECASE)


def shift_date(iso: str, sign: str, n: int, unit: str) -> str:
    """``iso`` plus or minus ``n`` days, months or years; a day past the
    month's end falls back to its last day, as SQL's interval arithmetic."""
    import datetime

    d = datetime.date.fromisoformat(iso)
    k = n if sign == "+" else -n
    unit = unit.lower()
    if unit == "day":
        return (d + datetime.timedelta(days=k)).isoformat()
    months = d.year * 12 + d.month - 1 + (k if unit == "month" else 12 * k)
    y, m = divmod(months, 12)
    nxt = datetime.date(y + (m == 11), (m + 1) % 12 + 1, 1)
    last = (nxt - datetime.timedelta(days=1)).day
    return datetime.date(y, m + 1, min(d.day, last)).isoformat()


# sqlite3 runs a correlated subquery once an outer row, scanning lineitem
# or partsupp each time: the reference states Q2, Q4, Q17, Q20, Q21 and
# Q22's correlated subqueries as the joins and IN lists they equal
_Q = r"'[^']*'"
_DECORRELATE = [
    # Q2: the least supply cost of each part in the region
    (re.compile(r"from part, supplier, partsupp, nation, region where (.*) "
                r"and ps_supplycost = \(select min\(ps_supplycost\) from "
                r"partsupp, supplier, nation, region where p_partkey = "
                r"ps_partkey and s_suppkey = ps_suppkey and s_nationkey = "
                r"n_nationkey and n_regionkey = r_regionkey and (r_name = "
                + _Q + r")\)"),
     r"from part, supplier, partsupp, nation, region, (select ps_partkey as "
     r"k2, min(ps_supplycost) as c2 from partsupp, supplier, nation, region "
     r"where s_suppkey = ps_suppkey and s_nationkey = n_nationkey and "
     r"n_regionkey = r_regionkey and \2 group by ps_partkey) as r2 where \1 "
     r"and k2 = p_partkey and ps_supplycost = c2"),
    # Q4: an order with a line committed before it was received
    (re.compile(r"exists \(select \* from lineitem where l_orderkey = "
                r"o_orderkey and l_commitdate < l_receiptdate\)"),
     r"o_orderkey in (select l_orderkey from lineitem "
     r"where l_commitdate < l_receiptdate)"),
    # Q17: a fifth of each part's mean quantity
    (re.compile(r"from lineitem, part where (.*) and l_quantity < \(select "
                r"0\.2 \* avg\(l_quantity\) from lineitem where l_partkey = "
                r"p_partkey\)"),
     r"from lineitem, part, (select l_partkey as k17, 0.2 * avg(l_quantity) "
     r"as q17 from lineitem group by l_partkey) as r17 where \1 and "
     r"k17 = p_partkey and l_quantity < q17"),
    # Q20: half of each part and supplier's quantity shipped in the year
    (re.compile(r"from partsupp where (.*) and ps_availqty > \(select 0\.5 "
                r"\* sum\(l_quantity\) from lineitem where l_partkey = "
                r"ps_partkey and l_suppkey = ps_suppkey and (l_shipdate >= "
                + _Q + r" and l_shipdate < " + _Q + r")\)\)"),
     r"from partsupp, (select l_partkey as p20, l_suppkey as s20, 0.5 * "
     r"sum(l_quantity) as q20 from lineitem where \2 group by l_partkey, "
     r"l_suppkey) as r20 where \1 and p20 = ps_partkey and s20 = ps_suppkey "
     r"and ps_availqty > q20)"),
    # Q21: orders of more than one supplier where only l1's was late
    (re.compile(r"from supplier, lineitem l1, orders, nation where (.*) and "
                r"exists \(select \* from lineitem l2 where l2\.l_orderkey = "
                r"l1\.l_orderkey and l2\.l_suppkey <> l1\.l_suppkey\) and not "
                r"exists \(select \* from lineitem l3 where l3\.l_orderkey = "
                r"l1\.l_orderkey and l3\.l_suppkey <> l1\.l_suppkey and "
                r"l3\.l_receiptdate > l3\.l_commitdate\)"),
     r"from supplier, lineitem l1, orders, nation where \1 and l1.l_orderkey "
     r"in (select l_orderkey from lineitem group by l_orderkey having "
     r"count(distinct l_suppkey) > 1) and l1.l_orderkey in (select "
     r"l_orderkey from lineitem where l_receiptdate > l_commitdate group by "
     r"l_orderkey having count(distinct l_suppkey) = 1)"),
    # Q22: a customer with no orders
    (re.compile(r"not exists \(select \* from orders where o_custkey = "
                r"c_custkey\)"),
     r"c_custkey not in (select o_custkey from orders)"),
]
_DERIVED_COLUMNS = re.compile(r"\)\s*as\s+(\w+)\s*\(([\w\s,]+)\)",
                              re.IGNORECASE)


def reference_sql(sql: str) -> str:
    """The reference's text of a statement: date arithmetic folded to a date
    string, ``extract(year from x)`` and ``substring(x from a for b)`` in
    sqlite3's words, DATE literals as the strings they compare with, and a
    derived table with a column list (Q13's) as a WITH query, which sqlite3
    names columns by, and the correlated subqueries of :data:`_DECORRELATE`
    as joins and IN lists."""
    sql = _INTERVAL.sub(lambda m: "'" + shift_date(
        m.group(1), m.group(2), int(m.group(3)), m.group(4)) + "'", sql)
    sql = _DATE.sub(r"'\1'", sql)
    sql = _EXTRACT.sub(r"cast(substr(\1, 1, 4) as integer)", sql)
    sql = _SUBSTRING.sub(r"substr(\1, \2, \3)", sql)
    sql = " ".join(sql.split())
    for pattern, text in _DECORRELATE:
        sql = pattern.sub(text, sql)
    m = _DERIVED_COLUMNS.search(sql)
    if m is None:
        return sql
    depth, start = 0, m.start()
    for i in range(m.start(), -1, -1):  # the derived table's "("
        depth += {")": 1, "(": -1}.get(sql[i], 0)
        if depth == 0:
            start = i
            break
    return (f"with {m.group(1)} ({m.group(2)}) as {sql[start:m.start() + 1]} "
            + reference_sql(sql[:start] + m.group(1) + sql[m.end():]))


def _ddl(name: str, cols: dict) -> str:
    types = {"i": "BIGINT", "u": "BIGINT", "f": "DECIMAL(15,2)",
             "U": "STRING"}
    decl = ", ".join(f"{c} {types[v.dtype.kind]}" for c, v in cols.items())
    return f"CREATE TABLE {name} ({decl})"


def load(wh, tables: dict) -> None:
    """Create the tables and insert every row through the warehouse's own
    ACID insert path, in one transaction."""
    from repro.core.acid import AcidTable
    from repro.core.runtime.vector import VectorBatch

    s = wh.session()
    for name, cols in tables.items():
        s.execute(_ddl(name, cols))
    hms = wh.hms
    tx = hms.open_txn()
    for name, cols in tables.items():
        AcidTable(hms.get_table(name), hms).insert(tx, VectorBatch(dict(cols)))
    hms.commit_txn(tx)


# The 22 queries of clause 2.4 with their validation parameters (the
# "Query Validation" substitutions of each definition), Q15 in its
# WITH variant (Appendix B).  "first n selected rows" is written LIMIT n.
PUBLISHED = {
    "q1": """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
        sum(l_extendedprice) as sum_base_price,
        sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
        sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
        avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
        avg(l_discount) as avg_disc, count(*) as count_order
        from lineitem
        where l_shipdate <= date '1998-12-01' - interval '90' day (3)
        group by l_returnflag, l_linestatus
        order by l_returnflag, l_linestatus""",
    "q2": """select s_acctbal, s_name, n_name, p_partkey, p_mfgr, s_address,
        s_phone, s_comment
        from part, supplier, partsupp, nation, region
        where p_partkey = ps_partkey and s_suppkey = ps_suppkey
        and p_size = 15 and p_type like '%BRASS'
        and s_nationkey = n_nationkey and n_regionkey = r_regionkey
        and r_name = 'EUROPE'
        and ps_supplycost = (select min(ps_supplycost)
            from partsupp, supplier, nation, region
            where p_partkey = ps_partkey and s_suppkey = ps_suppkey
            and s_nationkey = n_nationkey and n_regionkey = r_regionkey
            and r_name = 'EUROPE')
        order by s_acctbal desc, n_name, s_name, p_partkey
        limit 100""",
    "q3": """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
        o_orderdate, o_shippriority
        from customer, orders, lineitem
        where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
        and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
        and l_shipdate > date '1995-03-15'
        group by l_orderkey, o_orderdate, o_shippriority
        order by revenue desc, o_orderdate
        limit 10""",
    "q4": """select o_orderpriority, count(*) as order_count
        from orders
        where o_orderdate >= date '1993-07-01'
        and o_orderdate < date '1993-07-01' + interval '3' month
        and exists (select * from lineitem
            where l_orderkey = o_orderkey and l_commitdate < l_receiptdate)
        group by o_orderpriority
        order by o_orderpriority""",
    "q5": """select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
        from customer, orders, lineitem, supplier, nation, region
        where c_custkey = o_custkey and l_orderkey = o_orderkey
        and l_suppkey = s_suppkey and c_nationkey = s_nationkey
        and s_nationkey = n_nationkey and n_regionkey = r_regionkey
        and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
        and o_orderdate < date '1994-01-01' + interval '1' year
        group by n_name
        order by revenue desc""",
    "q6": """select sum(l_extendedprice * l_discount) as revenue
        from lineitem
        where l_shipdate >= date '1994-01-01'
        and l_shipdate < date '1994-01-01' + interval '1' year
        and l_discount between .06 - 0.01 and .06 + 0.01
        and l_quantity < 24""",
    "q7": """select supp_nation, cust_nation, l_year, sum(volume) as revenue
        from (select n1.n_name as supp_nation, n2.n_name as cust_nation,
            extract(year from l_shipdate) as l_year,
            l_extendedprice * (1 - l_discount) as volume
            from supplier, lineitem, orders, customer, nation n1, nation n2
            where s_suppkey = l_suppkey and o_orderkey = l_orderkey
            and c_custkey = o_custkey and s_nationkey = n1.n_nationkey
            and c_nationkey = n2.n_nationkey
            and ((n1.n_name = 'FRANCE' and n2.n_name = 'GERMANY')
                or (n1.n_name = 'GERMANY' and n2.n_name = 'FRANCE'))
            and l_shipdate between date '1995-01-01' and date '1996-12-31'
            ) as shipping
        group by supp_nation, cust_nation, l_year
        order by supp_nation, cust_nation, l_year""",
    "q8": """select o_year,
        sum(case when nation = 'BRAZIL' then volume else 0 end)
            / sum(volume) as mkt_share
        from (select extract(year from o_orderdate) as o_year,
            l_extendedprice * (1 - l_discount) as volume, n2.n_name as nation
            from part, supplier, lineitem, orders, customer, nation n1,
            nation n2, region
            where p_partkey = l_partkey and s_suppkey = l_suppkey
            and l_orderkey = o_orderkey and o_custkey = c_custkey
            and c_nationkey = n1.n_nationkey and n1.n_regionkey = r_regionkey
            and r_name = 'AMERICA' and s_nationkey = n2.n_nationkey
            and o_orderdate between date '1995-01-01' and date '1996-12-31'
            and p_type = 'ECONOMY ANODIZED STEEL') as all_nations
        group by o_year
        order by o_year""",
    "q9": """select nation, o_year, sum(amount) as sum_profit
        from (select n_name as nation, extract(year from o_orderdate) as o_year,
            l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity
                as amount
            from part, supplier, lineitem, partsupp, orders, nation
            where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
            and ps_partkey = l_partkey and p_partkey = l_partkey
            and o_orderkey = l_orderkey and s_nationkey = n_nationkey
            and p_name like '%green%') as profit
        group by nation, o_year
        order by nation, o_year desc""",
    "q10": """select c_custkey, c_name,
        sum(l_extendedprice * (1 - l_discount)) as revenue, c_acctbal, n_name,
        c_address, c_phone, c_comment
        from customer, orders, lineitem, nation
        where c_custkey = o_custkey and l_orderkey = o_orderkey
        and o_orderdate >= date '1993-10-01'
        and o_orderdate < date '1993-10-01' + interval '3' month
        and l_returnflag = 'R' and c_nationkey = n_nationkey
        group by c_custkey, c_name, c_acctbal, c_phone, n_name, c_address,
            c_comment
        order by revenue desc
        limit 20""",
    "q11": """select ps_partkey, sum(ps_supplycost * ps_availqty) as value
        from partsupp, supplier, nation
        where ps_suppkey = s_suppkey and s_nationkey = n_nationkey
        and n_name = 'GERMANY'
        group by ps_partkey
        having sum(ps_supplycost * ps_availqty) > (
            select sum(ps_supplycost * ps_availqty) * 0.0001
            from partsupp, supplier, nation
            where ps_suppkey = s_suppkey and s_nationkey = n_nationkey
            and n_name = 'GERMANY')
        order by value desc""",
    "q12": """select l_shipmode,
        sum(case when o_orderpriority = '1-URGENT'
            or o_orderpriority = '2-HIGH' then 1 else 0 end) as high_line_count,
        sum(case when o_orderpriority <> '1-URGENT'
            and o_orderpriority <> '2-HIGH' then 1 else 0 end) as low_line_count
        from orders, lineitem
        where o_orderkey = l_orderkey and l_shipmode in ('MAIL', 'SHIP')
        and l_commitdate < l_receiptdate and l_shipdate < l_commitdate
        and l_receiptdate >= date '1994-01-01'
        and l_receiptdate < date '1994-01-01' + interval '1' year
        group by l_shipmode
        order by l_shipmode""",
    "q13": """select c_count, count(*) as custdist
        from (select c_custkey, count(o_orderkey)
            from customer left outer join orders on c_custkey = o_custkey
            and o_comment not like '%special%requests%'
            group by c_custkey) as c_orders (c_custkey, c_count)
        group by c_count
        order by custdist desc, c_count desc""",
    "q14": """select 100.00 * sum(case when p_type like 'PROMO%'
            then l_extendedprice * (1 - l_discount) else 0 end)
            / sum(l_extendedprice * (1 - l_discount)) as promo_revenue
        from lineitem, part
        where l_partkey = p_partkey and l_shipdate >= date '1995-09-01'
        and l_shipdate < date '1995-09-01' + interval '1' month""",
    "q15": """with revenue0 (supplier_no, total_revenue) as (
            select l_suppkey, sum(l_extendedprice * (1 - l_discount))
            from lineitem
            where l_shipdate >= date '1996-01-01'
            and l_shipdate < date '1996-01-01' + interval '3' month
            group by l_suppkey)
        select s_suppkey, s_name, s_address, s_phone, total_revenue
        from supplier, revenue0
        where s_suppkey = supplier_no
        and total_revenue = (select max(total_revenue) from revenue0)
        order by s_suppkey""",
    "q16": """select p_brand, p_type, p_size, count(distinct ps_suppkey)
            as supplier_cnt
        from partsupp, part
        where p_partkey = ps_partkey and p_brand <> 'Brand#45'
        and p_type not like 'MEDIUM POLISHED%'
        and p_size in (49, 14, 23, 45, 19, 3, 36, 9)
        and ps_suppkey not in (select s_suppkey from supplier
            where s_comment like '%Customer%Complaints%')
        group by p_brand, p_type, p_size
        order by supplier_cnt desc, p_brand, p_type, p_size""",
    "q17": """select sum(l_extendedprice) / 7.0 as avg_yearly
        from lineitem, part
        where p_partkey = l_partkey and p_brand = 'Brand#23'
        and p_container = 'MED BOX'
        and l_quantity < (select 0.2 * avg(l_quantity) from lineitem
            where l_partkey = p_partkey)""",
    "q18": """select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
        sum(l_quantity)
        from customer, orders, lineitem
        where o_orderkey in (select l_orderkey from lineitem
            group by l_orderkey having sum(l_quantity) > 300)
        and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        order by o_totalprice desc, o_orderdate
        limit 100""",
    "q19": """select sum(l_extendedprice * (1 - l_discount)) as revenue
        from lineitem, part
        where (p_partkey = l_partkey and p_brand = 'Brand#12'
            and p_container in ('SM CASE', 'SM BOX', 'SM PACK', 'SM PKG')
            and l_quantity >= 1 and l_quantity <= 1 + 10
            and p_size between 1 and 5 and l_shipmode in ('AIR', 'AIR REG')
            and l_shipinstruct = 'DELIVER IN PERSON')
        or (p_partkey = l_partkey and p_brand = 'Brand#23'
            and p_container in ('MED BAG', 'MED BOX', 'MED PKG', 'MED PACK')
            and l_quantity >= 10 and l_quantity <= 10 + 10
            and p_size between 1 and 10 and l_shipmode in ('AIR', 'AIR REG')
            and l_shipinstruct = 'DELIVER IN PERSON')
        or (p_partkey = l_partkey and p_brand = 'Brand#34'
            and p_container in ('LG CASE', 'LG BOX', 'LG PACK', 'LG PKG')
            and l_quantity >= 20 and l_quantity <= 20 + 10
            and p_size between 1 and 15 and l_shipmode in ('AIR', 'AIR REG')
            and l_shipinstruct = 'DELIVER IN PERSON')""",
    "q20": """select s_name, s_address
        from supplier, nation
        where s_suppkey in (select ps_suppkey from partsupp
            where ps_partkey in (select p_partkey from part
                where p_name like 'forest%')
            and ps_availqty > (select 0.5 * sum(l_quantity) from lineitem
                where l_partkey = ps_partkey and l_suppkey = ps_suppkey
                and l_shipdate >= date '1994-01-01'
                and l_shipdate < date '1994-01-01' + interval '1' year))
        and s_nationkey = n_nationkey and n_name = 'CANADA'
        order by s_name""",
    "q21": """select s_name, count(*) as numwait
        from supplier, lineitem l1, orders, nation
        where s_suppkey = l1.l_suppkey and o_orderkey = l1.l_orderkey
        and o_orderstatus = 'F' and l1.l_receiptdate > l1.l_commitdate
        and exists (select * from lineitem l2
            where l2.l_orderkey = l1.l_orderkey
            and l2.l_suppkey <> l1.l_suppkey)
        and not exists (select * from lineitem l3
            where l3.l_orderkey = l1.l_orderkey
            and l3.l_suppkey <> l1.l_suppkey
            and l3.l_receiptdate > l3.l_commitdate)
        and s_nationkey = n_nationkey and n_name = 'SAUDI ARABIA'
        group by s_name
        order by numwait desc, s_name
        limit 100""",
    "q22": """select cntrycode, count(*) as numcust, sum(c_acctbal) as totacctbal
        from (select substring(c_phone from 1 for 2) as cntrycode, c_acctbal
            from customer
            where substring(c_phone from 1 for 2)
                in ('13', '31', '23', '29', '30', '18', '17')
            and c_acctbal > (select avg(c_acctbal) from customer
                where c_acctbal > 0.00 and substring(c_phone from 1 for 2)
                    in ('13', '31', '23', '29', '30', '18', '17'))
            and not exists (select * from orders
                where o_custkey = c_custkey)) as custsale
        group by cntrycode
        order by cntrycode""",
}


def _template(sql: str, subs: dict) -> str:
    """A published query with each validation parameter replaced by its
    ``{name}`` placeholder, on one line."""
    text = " ".join(sql.split())
    for old, name in subs.items():
        if old not in text:
            raise ValueError(f"{old!r} is not in the query")
        text = text.replace(old, "{" + name + "}")
    return text


# The same 22 queries with qgen's substitution parameters (clause 2.4's
# "Substitution Parameters" of each query).
TEMPLATES = {
    "q1": _template(PUBLISHED["q1"], {"'90'": "delta"}),
    "q2": _template(PUBLISHED["q2"], {"p_size = 15": "size_eq",
                                      "'%BRASS'": "type_like",
                                      "r_name = 'EUROPE'": "region_eq"}),
    "q3": _template(PUBLISHED["q3"], {"'BUILDING'": "segment",
                                      "'1995-03-15'": "date"}),
    "q4": _template(PUBLISHED["q4"], {"'1993-07-01'": "date"}),
    "q5": _template(PUBLISHED["q5"], {"'ASIA'": "region",
                                      "'1994-01-01'": "date"}),
    "q6": _template(PUBLISHED["q6"], {"'1994-01-01'": "date",
                                      ".06 - 0.01": "disc_lo",
                                      ".06 + 0.01": "disc_hi",
                                      "< 24": "qty_lt"}),
    "q7": _template(PUBLISHED["q7"], {
        "(n1.n_name = 'FRANCE' and n2.n_name = 'GERMANY') or "
        "(n1.n_name = 'GERMANY' and n2.n_name = 'FRANCE')": "nations"}),
    "q8": _template(PUBLISHED["q8"], {"'BRAZIL'": "nation",
                                      "'AMERICA'": "region",
                                      "'ECONOMY ANODIZED STEEL'": "type"}),
    "q9": _template(PUBLISHED["q9"], {"'%green%'": "color_like"}),
    "q10": _template(PUBLISHED["q10"], {"'1993-10-01'": "date"}),
    "q11": _template(PUBLISHED["q11"], {"'GERMANY'": "nation"}),
    "q12": _template(PUBLISHED["q12"], {"('MAIL', 'SHIP')": "shipmodes",
                                        "'1994-01-01'": "date"}),
    "q13": _template(PUBLISHED["q13"], {"'%special%requests%'": "words"}),
    "q14": _template(PUBLISHED["q14"], {"'1995-09-01'": "date"}),
    "q15": _template(PUBLISHED["q15"], {"'1996-01-01'": "date"}),
    "q16": _template(PUBLISHED["q16"], {
        "'Brand#45'": "brand", "'MEDIUM POLISHED%'": "type_like",
        "(49, 14, 23, 45, 19, 3, 36, 9)": "sizes"}),
    "q17": _template(PUBLISHED["q17"], {"'Brand#23'": "brand",
                                        "'MED BOX'": "container"}),
    "q18": _template(PUBLISHED["q18"], {"> 300": "qty_gt"}),
    "q19": _template(PUBLISHED["q19"], {
        "'Brand#12'": "brand1", "'Brand#23'": "brand2",
        "'Brand#34'": "brand3", ">= 1 and l_quantity <= 1 + 10": "qty1",
        ">= 10 and l_quantity <= 10 + 10": "qty2",
        ">= 20 and l_quantity <= 20 + 10": "qty3"}),
    "q20": _template(PUBLISHED["q20"], {"'forest%'": "color_like",
                                        "'1994-01-01'": "date",
                                        "'CANADA'": "nation"}),
    "q21": _template(PUBLISHED["q21"], {"'SAUDI ARABIA'": "nation"}),
    "q22": _template(PUBLISHED["q22"], {
        "('13', '31', '23', '29', '30', '18', '17')": "codes"}),
}


def _iso(rng, first: str, months: int) -> str:
    """The first of a month drawn from ``months`` months from ``first``."""
    return shift_date(first, "+", int(rng.integers(0, months)), "month")


def draw_params(rng: np.random.Generator) -> dict:
    """One draw of every substitution parameter over qgen's ranges, in the
    shapes of the published queries."""
    nation = int(rng.integers(0, 25))
    region = NATIONS[nation][1]
    pair = rng.choice(25, 2, replace=False)
    n1, n2 = NATIONS[int(pair[0])][0], NATIONS[int(pair[1])][0]
    modes = rng.choice(len(SHIPMODES), 2, replace=False)
    codes = rng.choice(25, 7, replace=False) + 10
    sizes_ = rng.choice(np.arange(1, 51), 8, replace=False)
    disc = int(rng.integers(2, 10))
    qty1 = int(rng.integers(1, 11))
    qty2 = int(rng.integers(10, 21))
    qty3 = int(rng.integers(20, 31))
    return {
        "delta": f"'{int(rng.integers(60, 121))}'",
        "size_eq": f"p_size = {int(rng.integers(1, 51))}",
        "type_like": f"'%{TYPES[2][int(rng.integers(0, 5))]}'",
        "region_eq": f"r_name = '{REGIONS[int(rng.integers(0, 5))]}'",
        "segment": f"'{SEGMENTS[int(rng.integers(0, 5))]}'",
        "date": f"'{_iso(rng, '1993-01-01', 58)}'",
        "region": f"'{REGIONS[region]}'",
        "disc_lo": f"{disc / 100:.2f} - 0.01",
        "disc_hi": f"{disc / 100:.2f} + 0.01",
        "qty_lt": f"< {int(rng.integers(24, 26))}",
        "nations": f"(n1.n_name = '{n1}' and n2.n_name = '{n2}') or "
                   f"(n1.n_name = '{n2}' and n2.n_name = '{n1}')",
        "nation": f"'{NATIONS[nation][0]}'",
        "type": "'" + " ".join(t[int(rng.integers(0, len(t)))]
                               for t in TYPES) + "'",
        "color_like": f"'%{COLORS[int(rng.integers(0, len(COLORS)))]}%'",
        "shipmodes": f"('{SHIPMODES[modes[0]]}', '{SHIPMODES[modes[1]]}')",
        "words": "'%{}%{}%'".format(
            ("special", "pending", "unusual", "express")[
                int(rng.integers(0, 4))],
            ("packages", "requests", "accounts", "deposits")[
                int(rng.integers(0, 4))]),
        "brand": f"'Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}'",
        "container": "'" + CONTAINERS[0][int(rng.integers(0, 5))] + " "
                     + CONTAINERS[1][int(rng.integers(0, 8))] + "'",
        "sizes": "(" + ", ".join(str(int(s)) for s in sizes_) + ")",
        "qty_gt": f"> {int(rng.integers(312, 316))}",
        "brand1": f"'Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}'",
        "brand2": f"'Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}'",
        "brand3": f"'Brand#{int(rng.integers(1, 6))}{int(rng.integers(1, 6))}'",
        "qty1": f">= {qty1} and l_quantity <= {qty1} + 10",
        "qty2": f">= {qty2} and l_quantity <= {qty2} + 10",
        "qty3": f">= {qty3} and l_quantity <= {qty3} + 10",
        "codes": "(" + ", ".join(f"'{int(c)}'" for c in codes) + ")",
    }
