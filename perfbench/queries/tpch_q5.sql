-- TPC-H v3.0.1, 2.4.5 Local Supplier Volume Query (Q5), as published, with
-- its validation parameters REGION = ASIA and DATE = 1994-01-01; the dates
-- are written as day numbers: 1994-01-01 is 8766 and 1994-01-01 plus one
-- year is 9131.
select
	n_name,
	sum(l_extendedprice * (1 - l_discount)) as revenue
from
	customer,
	orders,
	lineitem,
	supplier,
	nation,
	region
where
	c_custkey = o_custkey
	and l_orderkey = o_orderkey
	and l_suppkey = s_suppkey
	and c_nationkey = s_nationkey
	and s_nationkey = n_nationkey
	and n_regionkey = r_regionkey
	and r_name = 'ASIA'
	and o_orderdate >= 8766
	and o_orderdate < 9131
group by
	n_name
order by
	revenue desc;
