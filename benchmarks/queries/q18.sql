select c.name, c.custkey, o.orderkey, o.orderdate, o.totalprice,
       sum(l.quantity) as total_qty
from customer c, orders o, lineitem l
where o.orderkey in (
        select orderkey
        from lineitem
        group by orderkey
        having sum(quantity) > 300)
  and c.custkey = o.custkey
  and o.orderkey = l.orderkey
group by c.name, c.custkey, o.orderkey, o.orderdate, o.totalprice
order by o.totalprice desc, o.orderdate
limit 100
