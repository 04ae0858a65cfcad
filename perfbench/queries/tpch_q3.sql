-- TPC-H v3.0.1, 2.4.3 Shipping Priority Query (Q3), as published, with its
-- validation parameters SEGMENT = BUILDING and DATE = 1995-03-15; the date
-- is written as its day number (9204) and "the first 10 rows" as LIMIT 10.
select
	l_orderkey,
	sum(l_extendedprice * (1 - l_discount)) as revenue,
	o_orderdate,
	o_shippriority
from
	customer,
	orders,
	lineitem
where
	c_mktsegment = 'BUILDING'
	and c_custkey = o_custkey
	and l_orderkey = o_orderkey
	and o_orderdate < 9204
	and l_shipdate > 9204
group by
	l_orderkey,
	o_orderdate,
	o_shippriority
order by
	revenue desc,
	o_orderdate
limit 10;
