-- TPC-H v3.0.1, 2.4.6 Forecasting Revenue Change Query (Q6), as published,
-- with its validation parameters DATE = 1994-01-01, DISCOUNT = 0.06 and
-- QUANTITY = 24; the dates are written as day numbers (8766, and 9131 for
-- DATE + 1 year) and "between 0.06 - 0.01 and 0.06 + 0.01" as its two
-- comparisons.
select
	sum(l_extendedprice * l_discount) as revenue
from
	lineitem
where
	l_shipdate >= 8766
	and l_shipdate < 9131
	and l_discount >= 0.05
	and l_discount <= 0.07
	and l_quantity < 24;
