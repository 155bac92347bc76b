select sum(extendedprice * discount) as revenue
from lineitem
where shipdate >= date '1994-01-01'
  and shipdate < date '1995-01-01'
  and discount between 0.05 and 0.07
  and quantity < 24
