"""Star Schema Benchmark data, queries and query templates.

After P. O'Neil, E. O'Neil and X. Chen, "Star Schema Benchmark", revision 3
(2009), and its generator ``ssb-dbgen``: the five tables with the
specification's columns, value domains and sizes, and its 13 queries word
for word.  Every table is drawn from the seed: the dimensions from one
stream and lineorder from another, so a copy with fewer lineorder rows
holds the same dimensions.

Numbers follow ``ssb-dbgen``'s output: prices and costs are whole cents,
``lo_discount`` (0-10) and ``lo_tax`` (0-8) whole percents, and dates keys
of the form ``yyyymmdd``.  What the specification leaves to the generator
is listed under ``assumed`` in each configuration's file.

A configuration names this module as its ``"dataset"``; the harness asks it
for everything that is SSB's: ``generate``, ``warm_copy``, ``warm_bound``,
``load``, ``PUBLISHED``, ``TEMPLATES``, ``draw_params``, ``KEYS`` and
``reference_sql``.
"""
from __future__ import annotations

import numpy as np

# TPC-H's nations and regions, which SSB keeps: (nation, region) by key
NATIONS = (
    ("ALGERIA", "AFRICA"), ("ARGENTINA", "AMERICA"), ("BRAZIL", "AMERICA"),
    ("CANADA", "AMERICA"), ("EGYPT", "MIDDLE EAST"), ("ETHIOPIA", "AFRICA"),
    ("FRANCE", "EUROPE"), ("GERMANY", "EUROPE"), ("INDIA", "ASIA"),
    ("INDONESIA", "ASIA"), ("IRAN", "MIDDLE EAST"), ("IRAQ", "MIDDLE EAST"),
    ("JAPAN", "ASIA"), ("JORDAN", "MIDDLE EAST"), ("KENYA", "AFRICA"),
    ("MOROCCO", "AFRICA"), ("MOZAMBIQUE", "AFRICA"), ("PERU", "AMERICA"),
    ("CHINA", "ASIA"), ("ROMANIA", "EUROPE"), ("SAUDI ARABIA", "MIDDLE EAST"),
    ("VIETNAM", "ASIA"), ("RUSSIA", "EUROPE"), ("UNITED KINGDOM", "EUROPE"),
    ("UNITED STATES", "AMERICA"),
)
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
MONTHS = ("January", "February", "March", "April", "May", "June", "July",
          "August", "September", "October", "November", "December")
WEEKDAYS = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
            "Friday", "Saturday")
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
SHIPMODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
DATE_ROWS = 2556  # ssb-dbgen's date table: 1992-01-01 to 1998-12-30
REHEARSAL_DIMENSION_ROWS = 1000  # a rehearsal's cap on each dimension
LAST_ORDER_DAY = np.datetime64("1998-08-02")  # TPC-H's end date less 151 days


def city(nation: str, digit: int) -> str:
    """SSB's city: the nation's name cut or padded to 9 letters, then a
    digit."""
    return f"{nation[:9]:<9}{digit}"


def brand(category: int, number: int) -> str:
    return f"MFGR#{category}{number}"


def _text(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` random strings of ``lo`` to ``hi`` letters and digits."""
    alpha = np.array(list("abcdefghijklmnopqrstuvwxyz"
                          "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ,"))
    chars = alpha[rng.integers(0, len(alpha), (n, hi))]
    lens = rng.integers(lo, hi + 1, n)
    return np.array(["".join(c[:k]) for c, k in zip(chars, lens)])


def _phone(rng, nation: np.ndarray) -> np.ndarray:
    d = rng.integers((100, 100, 1000), (1000, 1000, 10000), (len(nation), 3))
    return np.array([f"{10 + k}-{a}-{b}-{c}"
                     for k, (a, b, c) in zip(nation.tolist(), d.tolist())])


def _dates() -> dict:
    day = np.datetime64("1992-01-01") + np.arange(DATE_ROWS)
    y = day.astype("datetime64[Y]").astype(int) + 1970
    m = day.astype("datetime64[M]").astype(int) % 12 + 1
    first_of_month = day.astype("datetime64[M]").astype("datetime64[D]")
    dom = (day - first_of_month).astype(int) + 1
    doy = (day - day.astype("datetime64[Y]").astype("datetime64[D]")
           ).astype(int) + 1
    dow = (day.astype(int) + 4) % 7  # 1970-01-01 was a Thursday; 0 = Sunday
    next_day = day + 1
    last_of_month = next_day.astype("datetime64[M]") != day.astype(
        "datetime64[M]")
    season = np.array(["Winter", "Winter", "Spring", "Spring", "Spring",
                       "Summer", "Summer", "Summer", "Fall", "Fall", "Fall",
                       "Christmas"])[m - 1]
    holiday = ((m == 1) & (dom == 1)) | ((m == 7) & (dom == 4)) \
        | ((m == 12) & (dom == 25)) | ((m == 11) & (dom == 11))
    months = np.array(MONTHS)
    return {
        "d_datekey": y * 10000 + m * 100 + dom,
        "d_date": np.array([f"{MONTHS[a - 1]} {b}, {c}"
                            for a, b, c in zip(m, dom, y)]),
        "d_dayofweek": np.array(WEEKDAYS)[dow],
        "d_month": months[m - 1],
        "d_year": y,
        "d_yearmonthnum": y * 100 + m,
        "d_yearmonth": np.array([f"{MONTHS[a - 1][:3]}{c}"
                                 for a, c in zip(m, y)]),
        "d_daynuminweek": dow + 1,
        "d_daynuminmonth": dom,
        "d_daynuminyear": doy,
        "d_monthnuminyear": m,
        "d_weeknuminyear": (doy - 1) // 7 + 1,
        "d_sellingseason": season,
        "d_lastdayinweekfl": (dow == 6).astype(np.int64),
        "d_lastdayinmonthfl": last_of_month.astype(np.int64),
        "d_holidayfl": holiday.astype(np.int64),
        "d_weekdayfl": ((dow >= 1) & (dow <= 5)).astype(np.int64),
    }


def _geography(rng, n: int) -> tuple:
    nation = rng.integers(0, 25, n)
    names = np.array([a for a, _ in NATIONS])
    regions = np.array([b for _, b in NATIONS])
    digit = rng.integers(0, 10, n)
    cities = np.array([city(names[k], d) for k, d in zip(nation, digit)])
    return nation, cities, names[nation], regions[nation]


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's part retail price, in cents, from the part key."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def generate(seed: int, config: dict, rehearse_rows=None) -> dict:
    """``{table: {column: ndarray}}`` for one seed, at the configuration's
    ``lineorder_rows`` and ``dimension_rows`` (of ``customer``,
    ``supplier`` and ``part``); a rehearsal draws ``rehearse_rows``
    lineorder rows and at most :data:`REHEARSAL_DIMENSION_ROWS` rows a
    dimension.  ``date`` always holds SSB's 2,556 days.
    """
    lineorder_rows = int(config["lineorder_rows"])
    dims = config["dimension_rows"]
    if rehearse_rows is not None:
        lineorder_rows = rehearse_rows
        dims = {k: min(v, REHEARSAL_DIMENSION_ROWS) for k, v in dims.items()}
    rng = np.random.default_rng([seed, 0])
    n_cust, n_supp, n_part = dims["customer"], dims["supplier"], dims["part"]
    tables = {"date": _dates()}

    c_nation, c_city, c_nat, c_reg = _geography(rng, n_cust)
    tables["customer"] = {
        "c_custkey": np.arange(1, n_cust + 1),
        "c_name": np.array([f"Customer#{k:09d}" for k in range(1, n_cust + 1)]),
        "c_address": _text(rng, n_cust, 10, 25),
        "c_city": c_city, "c_nation": c_nat, "c_region": c_reg,
        "c_phone": _phone(rng, c_nation),
        "c_mktsegment": np.array(SEGMENTS)[rng.integers(0, 5, n_cust)],
    }
    s_nation, s_city, s_nat, s_reg = _geography(rng, n_supp)
    tables["supplier"] = {
        "s_suppkey": np.arange(1, n_supp + 1),
        "s_name": np.array([f"Supplier#{k:09d}" for k in range(1, n_supp + 1)]),
        "s_address": _text(rng, n_supp, 10, 25),
        "s_city": s_city, "s_nation": s_nat, "s_region": s_reg,
        "s_phone": _phone(rng, s_nation),
    }
    mfgr = rng.integers(1, 6, n_part)
    category = mfgr * 10 + rng.integers(1, 6, n_part)
    number = rng.integers(1, 41, n_part)
    colors = np.array(COLORS)
    first, second = rng.integers(0, len(COLORS), (2, n_part))
    types = [np.array(t)[rng.integers(0, len(t), n_part)] for t in TYPES]
    conts = [np.array(t)[rng.integers(0, len(t), n_part)] for t in CONTAINERS]
    tables["part"] = {
        "p_partkey": np.arange(1, n_part + 1),
        "p_name": _tight(np.char.add(np.char.add(colors[first], " "),
                                     colors[second])),
        "p_mfgr": _tight(np.char.add("MFGR#", mfgr.astype(str))),
        "p_category": _tight(np.char.add("MFGR#", category.astype(str))),
        "p_brand1": _tight(np.char.add(
            np.char.add("MFGR#", category.astype(str)), number.astype(str))),
        "p_color": colors[rng.integers(0, len(COLORS), n_part)],
        "p_type": _tight(np.char.add(np.char.add(
            np.char.add(types[0], " "), np.char.add(types[1], " ")),
            types[2])),
        "p_size": rng.integers(1, 51, n_part),
        "p_container": _tight(np.char.add(np.char.add(conts[0], " "),
                                          conts[1])),
    }
    tables["lineorder"] = _lineorder(np.random.default_rng([seed, 1]),
                                     lineorder_rows, n_cust, n_supp, n_part,
                                     tables["date"]["d_datekey"])
    return tables


def _lineorder(rng, n: int, n_cust: int, n_supp: int, n_part: int,
               datekey: np.ndarray) -> dict:
    """``n`` lines of orders of 1 to 7 lines each, the last order cut."""
    per_order = rng.integers(1, 8, n)
    n_orders = int(np.searchsorted(np.cumsum(per_order), n)) + 1
    order = np.repeat(np.arange(n_orders), per_order[:n_orders])[:n]
    first_line = np.concatenate(([0], np.cumsum(per_order[:n_orders])[:-1]))
    order_days = int((LAST_ORDER_DAY - np.datetime64("1992-01-01"))
                     .astype(int)) + 1
    o_cust = rng.integers(1, n_cust + 1, n_orders)
    o_day = rng.integers(0, order_days, n_orders)
    o_prio = rng.integers(0, len(PRIORITIES), n_orders)
    partkey = rng.integers(1, n_part + 1, n)
    suppkey = rng.integers(1, n_supp + 1, n)
    quantity = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    commit = o_day[order] + rng.integers(30, 91, n)
    shipmode = rng.integers(0, len(SHIPMODES), n)
    retail = retail_cents(partkey)
    price = quantity * retail
    line_total = price * (100 - discount) * (100 + tax) // 10000
    order_total = np.bincount(order, weights=line_total,
                              minlength=n_orders).astype(np.int64)
    return {
        "lo_orderkey": order + 1,
        "lo_linenumber": np.arange(n) - first_line[order] + 1,
        "lo_custkey": o_cust[order],
        "lo_partkey": partkey,
        "lo_suppkey": suppkey,
        "lo_orderdate": datekey[o_day][order],
        "lo_orderpriority": np.array(PRIORITIES)[o_prio][order],
        "lo_shippriority": np.full(n, "0"),
        "lo_quantity": quantity,
        "lo_extendedprice": price,
        "lo_ordtotalprice": order_total[order],
        "lo_discount": discount,
        "lo_revenue": price * (100 - discount) // 100,
        "lo_supplycost": 6 * retail // 10,
        "lo_tax": tax,
        "lo_commitdate": datekey[commit],
        "lo_shipmode": np.array(SHIPMODES)[shipmode],
    }


def _tight(a: np.ndarray) -> np.ndarray:
    """Strings built by ``np.char`` at the width of their longest value."""
    return a.astype(f"U{int(np.char.str_len(a).max(initial=1))}")


def head(tables: dict, rows: int) -> dict:
    """The same tables with only the first ``rows`` lineorder rows."""
    out = dict(tables)
    out["lineorder"] = {c: v[:rows] for c, v in tables["lineorder"].items()}
    return out


def warm_copy(tables: dict, stripe_rows: int) -> dict:
    """The tables the warm-up runs on: the same dimensions, and lineorder's
    first full stripe and a last stripe as long as the real one's."""
    rows = len(tables["lineorder"]["lo_orderkey"])
    return head(tables, min(rows, stripe_rows
                            + (rows % stripe_rows or stripe_rows)))


def warm_bound(tables: dict) -> int:
    """The most keys any build side can hold: the rows of the largest of
    customer, supplier and part.  ``date``'s fixed 2,556 days are left out:
    part is larger at every configuration's size, and a rehearsal, which
    caps the others at 1,000, has no compile cache to fill."""
    return max(len(next(iter(tables[t].values())))
               for t in ("customer", "supplier", "part"))


# each dimension's key, which the reference declares its primary key
KEYS = {"date": "d_datekey", "customer": "c_custkey",
        "supplier": "s_suppkey", "part": "p_partkey"}


def reference_sql(sql: str) -> str:
    """The reference's text of a statement: SSB's SQL runs on sqlite3 as it
    is."""
    return sql


def _ddl(name: str, cols: dict) -> str:
    types = {"i": "BIGINT", "u": "BIGINT", "f": "DOUBLE", "U": "STRING"}
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


# The specification's 13 queries, word for word, but for one thing: flight
# 4 lists lineorder first in its FROM clause, where the specification lists
# it last.  The order of an inner join's tables does not change its answer;
# the warehouse's join ordering keeps a cross join of the first two tables
# when they share no predicate, and date x customer x supplier x part does
# not fit in memory.
PUBLISHED = {
    "q1.1": """select sum(lo_extendedprice*lo_discount) as revenue
        from lineorder, date
        where lo_orderdate = d_datekey and d_year = 1993
        and lo_discount between 1 and 3 and lo_quantity < 25""",
    "q1.2": """select sum(lo_extendedprice*lo_discount) as revenue
        from lineorder, date
        where lo_orderdate = d_datekey and d_yearmonthnum = 199401
        and lo_discount between 4 and 6
        and lo_quantity between 26 and 35""",
    "q1.3": """select sum(lo_extendedprice*lo_discount) as revenue
        from lineorder, date
        where lo_orderdate = d_datekey and d_weeknuminyear = 6
        and d_year = 1994 and lo_discount between 5 and 7
        and lo_quantity between 26 and 35""",
    "q2.1": """select sum(lo_revenue), d_year, p_brand1
        from lineorder, date, part, supplier
        where lo_orderdate = d_datekey and lo_partkey = p_partkey
        and lo_suppkey = s_suppkey and p_category = 'MFGR#12'
        and s_region = 'AMERICA'
        group by d_year, p_brand1 order by d_year, p_brand1""",
    "q2.2": """select sum(lo_revenue), d_year, p_brand1
        from lineorder, date, part, supplier
        where lo_orderdate = d_datekey and lo_partkey = p_partkey
        and lo_suppkey = s_suppkey
        and p_brand1 between 'MFGR#2221' and 'MFGR#2228'
        and s_region = 'ASIA'
        group by d_year, p_brand1 order by d_year, p_brand1""",
    "q2.3": """select sum(lo_revenue), d_year, p_brand1
        from lineorder, date, part, supplier
        where lo_orderdate = d_datekey and lo_partkey = p_partkey
        and lo_suppkey = s_suppkey and p_brand1 = 'MFGR#2239'
        and s_region = 'EUROPE'
        group by d_year, p_brand1 order by d_year, p_brand1""",
    "q3.1": """select c_nation, s_nation, d_year, sum(lo_revenue) as revenue
        from customer, lineorder, supplier, date
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_orderdate = d_datekey and c_region = 'ASIA'
        and s_region = 'ASIA' and d_year >= 1992 and d_year <= 1997
        group by c_nation, s_nation, d_year
        order by d_year asc, revenue desc""",
    "q3.2": """select c_city, s_city, d_year, sum(lo_revenue) as revenue
        from customer, lineorder, supplier, date
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_orderdate = d_datekey and c_nation = 'UNITED STATES'
        and s_nation = 'UNITED STATES' and d_year >= 1992 and d_year <= 1997
        group by c_city, s_city, d_year
        order by d_year asc, revenue desc""",
    "q3.3": """select c_city, s_city, d_year, sum(lo_revenue) as revenue
        from customer, lineorder, supplier, date
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_orderdate = d_datekey
        and (c_city='UNITED KI1' or c_city='UNITED KI5')
        and (s_city='UNITED KI1' or s_city='UNITED KI5')
        and d_year >= 1992 and d_year <= 1997
        group by c_city, s_city, d_year
        order by d_year asc, revenue desc""",
    "q3.4": """select c_city, s_city, d_year, sum(lo_revenue) as revenue
        from customer, lineorder, supplier, date
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_orderdate = d_datekey
        and (c_city='UNITED KI1' or c_city='UNITED KI5')
        and (s_city='UNITED KI1' or s_city='UNITED KI5')
        and d_yearmonth = 'Dec1997'
        group by c_city, s_city, d_year
        order by d_year asc, revenue desc""",
    "q4.1": """select d_year, c_nation, sum(lo_revenue - lo_supplycost) as profit
        from lineorder, date, customer, supplier, part
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_partkey = p_partkey and lo_orderdate = d_datekey
        and c_region = 'AMERICA' and s_region = 'AMERICA'
        and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
        group by d_year, c_nation order by d_year, c_nation""",
    "q4.2": """select d_year, s_nation, p_category,
        sum(lo_revenue - lo_supplycost) as profit
        from lineorder, date, customer, supplier, part
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_partkey = p_partkey and lo_orderdate = d_datekey
        and c_region = 'AMERICA' and s_region = 'AMERICA'
        and (d_year = 1997 or d_year = 1998)
        and (p_mfgr = 'MFGR#1' or p_mfgr = 'MFGR#2')
        group by d_year, s_nation, p_category
        order by d_year, s_nation, p_category""",
    "q4.3": """select d_year, s_city, p_brand1,
        sum(lo_revenue - lo_supplycost) as profit
        from lineorder, date, customer, supplier, part
        where lo_custkey = c_custkey and lo_suppkey = s_suppkey
        and lo_partkey = p_partkey and lo_orderdate = d_datekey
        and c_region = 'AMERICA' and s_nation = 'UNITED STATES'
        and (d_year = 1997 or d_year = 1998) and p_category = 'MFGR#14'
        group by d_year, s_city, p_brand1
        order by d_year, s_city, p_brand1""",
}

# The same 13 queries with their substitution parameters, one line each, so
# that no filled template is ever the text of a published query.
_JOIN3 = ("from customer, lineorder, supplier, date where lo_custkey = "
          "c_custkey and lo_suppkey = s_suppkey and lo_orderdate = d_datekey")
_JOIN4 = ("from lineorder, date, customer, supplier, part where lo_custkey = "
          "c_custkey and lo_suppkey = s_suppkey and lo_partkey = p_partkey "
          "and lo_orderdate = d_datekey")
_JOIN2 = ("from lineorder, date, part, supplier where lo_orderdate = "
          "d_datekey and lo_partkey = p_partkey and lo_suppkey = s_suppkey")
_CITIES = ("(c_city='{city}' or c_city='{city2}') and "
           "(s_city='{city}' or s_city='{city2}')")
TEMPLATES = {
    "q1.1": "select sum(lo_extendedprice*lo_discount) as revenue from lineorder, date where lo_orderdate = d_datekey and d_year = {year} and lo_discount between {disc_lo} and {disc_hi} and lo_quantity < {qty}",
    "q1.2": "select sum(lo_extendedprice*lo_discount) as revenue from lineorder, date where lo_orderdate = d_datekey and d_yearmonthnum = {yearmonthnum} and lo_discount between {disc_lo} and {disc_hi} and lo_quantity between {qty_lo} and {qty_hi}",
    "q1.3": "select sum(lo_extendedprice*lo_discount) as revenue from lineorder, date where lo_orderdate = d_datekey and d_weeknuminyear = {week} and d_year = {year} and lo_discount between {disc_lo} and {disc_hi} and lo_quantity between {qty_lo} and {qty_hi}",
    "q2.1": "select sum(lo_revenue), d_year, p_brand1 " + _JOIN2 + " and p_category = '{category}' and s_region = '{region}' group by d_year, p_brand1 order by d_year, p_brand1",
    "q2.2": "select sum(lo_revenue), d_year, p_brand1 " + _JOIN2 + " and p_brand1 between '{brand_lo}' and '{brand_hi}' and s_region = '{region}' group by d_year, p_brand1 order by d_year, p_brand1",
    "q2.3": "select sum(lo_revenue), d_year, p_brand1 " + _JOIN2 + " and p_brand1 = '{brand}' and s_region = '{region}' group by d_year, p_brand1 order by d_year, p_brand1",
    "q3.1": "select c_nation, s_nation, d_year, sum(lo_revenue) as revenue " + _JOIN3 + " and c_region = '{region}' and s_region = '{region}' and d_year >= {year_lo} and d_year <= {year_hi} group by c_nation, s_nation, d_year order by d_year asc, revenue desc",
    "q3.2": "select c_city, s_city, d_year, sum(lo_revenue) as revenue " + _JOIN3 + " and c_nation = '{nation}' and s_nation = '{nation}' and d_year >= {year_lo} and d_year <= {year_hi} group by c_city, s_city, d_year order by d_year asc, revenue desc",
    "q3.3": "select c_city, s_city, d_year, sum(lo_revenue) as revenue " + _JOIN3 + " and " + _CITIES + " and d_year >= {year_lo} and d_year <= {year_hi} group by c_city, s_city, d_year order by d_year asc, revenue desc",
    "q3.4": "select c_city, s_city, d_year, sum(lo_revenue) as revenue " + _JOIN3 + " and " + _CITIES + " and d_yearmonth = '{yearmonth}' group by c_city, s_city, d_year order by d_year asc, revenue desc",
    "q4.1": "select d_year, c_nation, sum(lo_revenue - lo_supplycost) as profit " + _JOIN4 + " and c_region = '{region}' and s_region = '{region}' and (p_mfgr = '{mfgr}' or p_mfgr = '{mfgr2}') group by d_year, c_nation order by d_year, c_nation",
    "q4.2": "select d_year, s_nation, p_category, sum(lo_revenue - lo_supplycost) as profit " + _JOIN4 + " and c_region = '{region}' and s_region = '{region}' and (d_year = {year} or d_year = {year_next}) and (p_mfgr = '{mfgr}' or p_mfgr = '{mfgr2}') group by d_year, s_nation, p_category order by d_year, s_nation, p_category",
    "q4.3": "select d_year, s_city, p_brand1, sum(lo_revenue - lo_supplycost) as profit " + _JOIN4 + " and c_region = '{region}' and s_nation = '{nation_in_region}' and (d_year = {year} or d_year = {year_next}) and p_category = '{category}' group by d_year, s_city, p_brand1 order by d_year, s_city, p_brand1",
}


def draw_params(rng: np.random.Generator) -> dict:
    """One draw of every substitution parameter, over SSB's domains: years,
    months and weeks, discount and quantity ranges, regions, nations,
    cities, manufacturers, categories and brands, in the shapes of the
    published queries."""
    year = int(rng.integers(1992, 1998))
    month = int(rng.integers(1, 13))
    disc = int(rng.integers(0, 9))
    qty_lo = int(rng.integers(1, 42))
    year_lo = int(rng.integers(1992, 1996))
    region = str(rng.choice(REGIONS))
    nation = NATIONS[int(rng.integers(0, 25))][0]
    in_region = [n for n, r in NATIONS if r == region]
    digits = rng.choice(10, 2, replace=False)
    mfgr = rng.choice(np.arange(1, 6), 2, replace=False)
    category = int(rng.integers(1, 6)) * 10 + int(rng.integers(1, 6))
    number = int(rng.integers(10, 34))  # two digits, as 'MFGR#2221'
    return {
        "year": year,
        "year_next": year + 1,
        "yearmonthnum": year * 100 + month,
        "yearmonth": f"{MONTHS[month - 1][:3]}{year}",
        "week": int(rng.integers(1, 53)),
        "disc_lo": disc,
        "disc_hi": disc + 2,
        "qty": int(rng.integers(20, 31)),
        "qty_lo": qty_lo,
        "qty_hi": qty_lo + 9,
        "year_lo": year_lo,
        "year_hi": year_lo + int(rng.integers(2, 6)),
        "region": region,
        "nation": nation,
        "nation_in_region": str(rng.choice(in_region)),
        "city": city(nation, int(digits[0])),
        "city2": city(nation, int(digits[1])),
        "mfgr": f"MFGR#{int(mfgr[0])}",
        "mfgr2": f"MFGR#{int(mfgr[1])}",
        "category": f"MFGR#{category}",
        "brand": brand(category, int(rng.integers(1, 41))),
        "brand_lo": brand(category, number),
        "brand_hi": brand(category, number + 7),
    }
